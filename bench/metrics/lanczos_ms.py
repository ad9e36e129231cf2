"""Device time of the Lanczos factorization per prompt admitted in the
traced span: the leaf ops under the ``dcom.lanczos`` named scope
(``models/decomposed_kv.prefill_dkv``), in every program, over the
prompts that steps begun in the span admitted
(``Record.admitted_in_span``).  Bucket, batch and lane padding are part of
that time."""

UNIT = "ms"
SCOPE = "dcom.lanczos"


def read(rec):
    by = (rec.trace or {}).get("scope_seconds") or {}
    s = sum(p.get(SCOPE, 0.0) for p in by.values())
    n = len(rec.admitted_in_span())
    return s / n * 1e3 if s > 0 and n else None
