"""Share of the traced span in which no operation ran on the device:
one minus the union of the device-op intervals over the window."""

UNIT = "%"


def read(rec):
    t = rec.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
