"""95th percentile, over every delivery after a request's first, of the
time since that request's previous delivery: the gap a streaming client
sees, admission stalls included.  Over the window's requests, and only
gaps that end before the window's close and do not span the start of a
traced span: the profiler stalls the host where it starts and stops."""
import numpy as np

UNIT = "ms"


def read(rec):
    lo = rec.span[0]
    gaps = [b[0] - a[0] for r in rec.measured
            for a, b in zip(r.deliveries, r.deliveries[1:])
            if b[0] < rec.seconds and not (lo > 0 and a[0] < lo <= b[0])]
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
