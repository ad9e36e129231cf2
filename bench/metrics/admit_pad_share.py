"""Share of padding in the prefill token matrices that steps begun in
the traced span launched: each prompt's left padding to its bucket, and
the rows of a batch rounded up to a power of two.  Read from the engine's
``serving_prefill_tokens_total{kind="prompt"|"pad"}`` counters."""

UNIT = "%"


def read(rec):
    c = (rec.trace or {}).get("prefill_tokens") or {}
    total = c.get("prompt", 0) + c.get("pad", 0)
    return 100.0 * c["pad"] / total if total else None
