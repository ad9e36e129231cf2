"""Share of the traced span in which the device is idle while the engine
is inside a step: the idle gaps whose midpoint lies in an ``engine.step``
host span (the engine's own annotations, ``repro.obs.trace``), over the
span.  Every engine span nests inside a step, so that is each gap the
trace's ``idle_by_engine`` labels with an engine span.  Nothing where the
trace holds no engine span."""

UNIT = "%"
OUTSIDE = "outside engine"


def read(rec):
    t = rec.trace
    by = (t or {}).get("idle_by_engine") or {}
    if not any(k != OUTSIDE for k in by) or t["window_s"] <= 0:
        return None
    idle = sum(v for k, v in by.items() if k != OUTSIDE)
    return 100.0 * idle / t["window_s"]
