"""Device time of the fused decode-block programs in the traced span,
per decode round (one token for every live slot) of the steps begun in
it.  The programs are the
jitted ``run`` of the decomposed-KV decode block, ``jit_run`` in the
trace's program line."""
import re

UNIT = "ms"
PROGRAM = re.compile(r"^jit_run\b")


def read(rec):
    t = rec.trace
    if not t or rec.decode_rounds <= 0:
        return None
    s = sum(v for k, v in t["modules"].items() if PROGRAM.search(k))
    return s / rec.decode_rounds * 1e3 if s > 0 else None
