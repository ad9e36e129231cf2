"""Time per output token after the first delivery: the sum over the
window's requests of (last delivery − first delivery) over the sum of the
tokens delivered after the first delivery.  Deliveries are stamped when
``Engine.step()`` returns, so every stall between decode launches counts."""

UNIT = "ms"


def read(rec):
    span, toks = 0.0, 0
    for r in rec.measured:
        if len(r.deliveries) < 2:
            continue
        span += r.deliveries[-1][0] - r.deliveries[0][0]
        toks += sum(n for _, n in r.deliveries[1:])
    return span / toks * 1e3 if toks else None
