"""Model FLOP utilization of admission: the forward FLOPs of the real
(unpadded) prompt tokens admitted in the traced span, over the device time
of every program there but the decode block (``jit_run`` in the trace's
program line): the prefill programs, with the Lanczos kernel inside them,
and the splice and first-token programs around them.  Times the chip's
bf16 peak.  Lanczos, splice and bucket and batch padding are time, not
work, here.  The FLOPs are the model's block's ``forward_flops``."""
import re

from bench import counts

UNIT = "%"
DECODE = re.compile(r"^jit_run\b")


def read(rec):
    t = rec.trace
    if not t:
        return None
    secs = sum(v for k, v in t["modules"].items() if not DECODE.search(k))
    reqs = rec.admitted_in_span()
    if secs <= 0 or not reqs:
        return None
    flops = sum(rec.block.forward_flops(rec.model, r.prompt_len)
                for r in reqs)
    peak = counts.peaks(rec.device_kind)["bf16_flops"]
    return 100.0 * flops / (secs * peak)
