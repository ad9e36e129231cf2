"""90th percentile of time to first token over the requests due in the
window, from each request's due time to the delivery of its first token,
on the benchmark's clock.  A request that never delivered counts as
waiting until the harness gave up on it."""
import numpy as np

UNIT = "ms"


def read(rec):
    give_up = rec.seconds + 90.0
    vals = [((r.deliveries[0][0] if r.deliveries else give_up) - r.due)
            for r in rec.measured]
    return float(np.percentile(vals, 90)) * 1e3 if vals else None
