"""Single derivation point for the Pallas ``interpret`` flag.

The flag is derived ONCE from the platform: interpret mode everywhere
except a real TPU, where the same BlockSpecs compile via Mosaic with no
manual flags at call sites.

Kernel modules resolve their ``interpret=None`` default through
:func:`resolve_interpret`; ``kernels.ops`` seeds its module-level
``INTERPRET`` escape hatch from :func:`default_interpret`.  The answer is
memoized — the process's device set is fixed after jax initializes, so a
per-call re-check would only add dispatch latency.
"""
from __future__ import annotations

import functools
from typing import Optional


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """True unless this process runs on a real TPU."""
    import jax
    return jax.default_backend() != "tpu"


def resolve_interpret(flag: Optional[bool]) -> bool:
    """None → the platform default; an explicit flag always wins."""
    return default_interpret() if flag is None else bool(flag)


def kernel_backend() -> str:
    """The engine backend that runs the Pallas kernels on this platform:
    compiled ``pallas`` on a TPU, ``pallas_interpret`` elsewhere."""
    return "pallas_interpret" if default_interpret() else "pallas"
