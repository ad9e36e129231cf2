"""Process start to window open: imports, weights, engine, compilation or
loading from the persistent cache, and warm-up of every window shape."""

UNIT = "s"


def read(rec):
    return rec.setup_s
