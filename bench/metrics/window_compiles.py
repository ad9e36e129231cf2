"""XLA backend compiles inside the measured window (the benchmark's own
``jax.monitoring`` listener); warm-up should leave none."""

UNIT = "count"


def read(rec):
    return rec.compiles.get("window", 0)
