"""90th percentile of queue wait: from the due time to the engine's own
dispatch stamp (``Request.t_dispatch``), when the admission that takes the
request starts.  Over the window's requests due before the traced span:
the profiler's stop at the window's close stalls the host, and the wait
of a request still queued then would count that stall."""
import numpy as np

UNIT = "ms"


def read(rec):
    lo = rec.span[0]
    vals = [r.dispatch - r.due for r in rec.measured
            if r.dispatch is not None and (lo <= 0 or r.due < lo)]
    return float(np.percentile(vals, 90)) * 1e3 if vals else None
