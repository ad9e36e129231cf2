"""Share of its roofline the Lanczos re-orthogonalization kernel reaches:
the least time the chip needs for the work the algorithm needs, over the
summed device time of the kernel's events in the traced window.

The work is counted from the real, unpadded prompts admitted in the
traced span by the model's block (``reorth_needed`` in
``bench/blocks/<block>.py``): for the dense decoder, per layer, K and V
of ``prompt × kv width``, ``rank + kv_iters_extra`` steps of two matvecs
that each read the activation once in the dtype prefill wrote, plus the
Lanczos basis.  Bucket, batch and lane padding and re-reads of the
activation count as time, not as work."""
import re

from bench import counts

UNIT = "%"
KERNEL = re.compile(r"reorth", re.IGNORECASE)


def read(rec):
    t = rec.trace
    if not t:
        return None
    k_s = sum(v for k, v in t["ops"].items() if KERNEL.search(k))
    if k_s <= 0:
        return None
    ec = rec.engine_cfg
    need = 0.0
    for r in rec.admitted_in_span():
        fl, by = rec.block.reorth_needed(rec.model, r.prompt_len,
                                         int(ec["kv_rank"]),
                                         int(ec["kv_iters_extra"]))
        need += counts.roofline_seconds(fl, by, rec.device_kind)[0]
    return 100.0 * need / k_s if need > 0 else None
