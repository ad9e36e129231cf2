"""Output tokens delivered inside the window, to any request, per second
of the window."""

UNIT = "tokens/s"


def read(rec):
    n = sum(c for r in rec.requests for t, c in r.deliveries
            if t < rec.seconds)
    return n / rec.seconds if n else None
