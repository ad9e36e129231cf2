"""Backend-dispatch registry for the decomposition pipeline.

A backend decides HOW the batched Lanczos inner steps execute; it is
selected ONCE per engine (not per op, not per callsite):

* ``reference``        — pure-jnp batched einsum steps (always available,
                         the numerical oracle).
* ``pallas_interpret`` — the fused D-com re-orth kernel with the batch axis
                         in the Pallas grid, interpreter mode (CPU
                         containers / CI).
* ``pallas``           — same kernels compiled via Mosaic (TPU deployment).
* ``pallas_vmap``      — vmap-of-scalar-kernel fallback: the pre-engine
                         batching scheme, kept for A/B benchmarking and as
                         an escape hatch (interpret mode derived from the
                         platform, like ``kernels.ops``).

Hook factories are lru-cached upstream, so ``make_hooks`` returns stable
function identities — they are static jit arguments in ``core.lanczos``.
New backends (e.g. a sharded decomposition backend) register themselves
with :func:`register_backend`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict

from ..core.lanczos import (DEFAULT_BATCHED_HOOKS, BatchedLanczosHooks)


@dataclasses.dataclass(frozen=True)
class Backend:
    """One way of executing the batched Lanczos inner steps."""
    name: str
    make_hooks: Callable[[int], BatchedLanczosHooks]   # expansion -> hooks
    requires_padding: bool      # S, H padded by kernels.ops.padded_dims
    batched_launch: bool        # True: one kernel launch covers the batch


_REGISTRY: Dict[str, Backend] = {}


def register_backend(backend: Backend) -> Backend:
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown decompose backend {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def available_backends():
    return sorted(_REGISTRY)


def _reference_hooks(expansion: int) -> BatchedLanczosHooks:
    del expansion                       # reference steps need no blocking
    return DEFAULT_BATCHED_HOOKS


def _pallas_interpret_hooks(expansion: int) -> BatchedLanczosHooks:
    from ..kernels import ops
    return ops.make_batched_pallas_hooks(expansion, interpret=True)


def _pallas_hooks(expansion: int) -> BatchedLanczosHooks:
    from ..kernels import ops
    return ops.make_batched_pallas_hooks(expansion, interpret=False)


def _pallas_vmap_hooks(expansion: int) -> BatchedLanczosHooks:
    from ..kernels import ops
    return ops.make_vmapped_pallas_hooks(expansion)


register_backend(Backend("reference", _reference_hooks,
                         requires_padding=False, batched_launch=True))
register_backend(Backend("pallas_interpret", _pallas_interpret_hooks,
                         requires_padding=True, batched_launch=True))
register_backend(Backend("pallas", _pallas_hooks,
                         requires_padding=True, batched_launch=True))
register_backend(Backend("pallas_vmap", _pallas_vmap_hooks,
                         requires_padding=True, batched_launch=False))
