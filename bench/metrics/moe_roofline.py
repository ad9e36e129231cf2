"""Share of its roofline the held experts' grouped matmuls reach: the
least time the chip needs for the held experts' work in the traced span,
over the summed device time of the grouped-matmul events there.

The events are XLA's grouped matmul, which the trace shows under the
instruction name ``ragged-dot`` (``%ragged-dot-none.<n>``, the TPU
compiler's custom call, and ``%ragged-dot-metadata.<n>``, which builds its
tile schedule).  The admission and the decode programs both run the
expert layer and give its instructions the same names, and the trace's
op keys name no program, so the events of both count, and the work of
both (``bench/blocks/<block>.py``):

* each prompt admitted in the span, ``expert_needed`` at its real,
  unpadded length: the matmuls of its expected held picks and their rows
  (the weights are left out: one read of them serves a whole batch);
* each decode round of the steps begun in the span,
  ``expert_round_needed`` at the round's live tokens (what the step
  delivered by decode, over its rounds): above all one read of each held
  expert that those tokens are expected to pick.

Bucket and batch padding, the picks of experts held elsewhere (sorted
past every run), the weights of experts no token picked, and re-reads
count as time, not as work."""
import re

from bench import counts

UNIT = "%"
KERNEL = re.compile(r"ragged-dot")


def decode_rounds(rec):
    """(rounds, live tokens a round) of each step begun in the span that
    decoded: the tokens it delivered, less each request's first token
    (admission's), over its rounds."""
    lo, hi = rec.span
    by_end = {}
    for r in rec.requests:
        for i, (t, n) in enumerate(r.deliveries):
            by_end[t] = by_end.get(t, 0) + n - (i == 0)
    return [(k, by_end.get(t1, 0) / k) for t0, t1, _, k in rec.steps
            if k and lo <= t0 < hi]


def read(rec):
    t = rec.trace
    admit = getattr(rec.block, "expert_needed", None)
    per_round = getattr(rec.block, "expert_round_needed", None)
    if not t or admit is None or per_round is None:
        return None
    k_s = sum(v for k, v in t["ops"].items() if KERNEL.search(k))
    if k_s <= 0:
        return None

    def least(work):
        return counts.roofline_seconds(*work, rec.device_kind)[0]

    need = sum(least(admit(rec.model, r.prompt_len))
               for r in rec.admitted_in_span())
    need += sum(k * least(per_round(rec.model, live))
                for k, live in decode_rounds(rec) if live > 0)
    return 100.0 * need / k_s if need > 0 else None
