"""Jit'd public wrappers around the Pallas kernels + Lanczos hook factory.

``INTERPRET`` is derived ONCE from the platform (``engine.platform``):
interpret mode everywhere except a real TPU, where the same BlockSpecs
compile via Mosaic with no manual flags at call sites.  It stays a mutable
module attribute as the process-wide escape hatch (e.g. forcing interpret
mode on TPU for debugging).

Block sizes (``row_block``/``n_block``/``col_block``) default to ``None``
= the kernel's historical 512; the ``repro.tune`` autotuner passes the
measured operating point through these wrappers.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.lanczos import BatchedLanczosHooks, LanczosHooks
from ..engine.platform import default_interpret
from . import dkv_attention as _dkv, lanczos_reorth, \
    lowrank_matmul as _lrmm, matvec_expand, outlier_extract, ssd_chunk

INTERPRET = default_interpret()


@functools.lru_cache(maxsize=None)
def pad_plan(shape: tuple, axis: int, mult: int):
    """Cached pad decision for one axis: (pad widths tuple | None, orig n).

    Keyed on ``(shape, axis, mult)`` so repeated wrapper calls (and the
    engine's per-layer decompose sites) never recompute pad widths or build
    fresh width lists at trace time.
    """
    n = shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return None, n
    widths = [(0, 0)] * len(shape)
    widths[axis] = (0, pad)
    return tuple(widths), n


@functools.lru_cache(maxsize=None)
def padded_dims(s: int, h: int, expansion: int):
    """Cached (S_pad, H_pad) for a fused-Lanczos launch: the left step
    splits S into f row blocks of whole sublane tiles, the right step H
    into f column blocks of whole lane tiles (what the compiled kernel
    needs; zero pads are exact, so interpret mode takes the same plan)."""
    sm = lanczos_reorth.SUBLANE * expansion
    hm = lanczos_reorth.LANE * expansion
    return s + ((-s) % sm), h + ((-h) % hm)


def _pad_to(x: jax.Array, axis: int, mult: int):
    widths, n = pad_plan(x.shape, axis, mult)
    if widths is None:
        return x, n
    return jnp.pad(x, widths), n


def matvec(a, v, *, expansion: int = 8, row_block: Optional[int] = None,
           interpret: Optional[bool] = None):
    a, s = _pad_to(a, 0, 8)
    a, _ = _pad_to(a, 1, expansion)
    v, _ = _pad_to(v, 0, expansion)
    rb = min(row_block or 512, a.shape[0])
    y = matvec_expand.matvec(a, v, expansion=expansion, row_block=rb,
                             interpret=INTERPRET if interpret is None else interpret)
    return y[:s]


def rmatvec(a, u, *, expansion: int = 8, col_block: Optional[int] = None,
            interpret: Optional[bool] = None):
    a, _ = _pad_to(a, 0, expansion)
    a, h = _pad_to(a, 1, 128)
    u, _ = _pad_to(u, 0, expansion)
    cb = min(col_block or 512, a.shape[1])
    z = matvec_expand.rmatvec(a, u, expansion=expansion, col_block=cb,
                              interpret=INTERPRET if interpret is None else interpret)
    return z[:h]


def matvec_batched(a, v, *, expansion: int = 8,
                   row_block: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """y[B,S] = A[B,S,H] @ v[B,H]; pads H like the scalar wrapper."""
    a, _ = _pad_to(a, 2, expansion)
    v, _ = _pad_to(v, 1, expansion)
    y = matvec_expand.matvec_batched(
        a, v, expansion=expansion, row_block=min(row_block or 512,
                                                 a.shape[-2]),
        interpret=INTERPRET if interpret is None else interpret)
    return y


def rmatvec_batched(a, u, *, expansion: int = 8,
                    col_block: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """z[B,H] = A[B,S,H]ᵀ @ u[B,S]; pads S like the scalar wrapper."""
    a, _ = _pad_to(a, 1, expansion)
    u, _ = _pad_to(u, 1, expansion)
    z = matvec_expand.rmatvec_batched(
        a, u, expansion=expansion, col_block=min(col_block or 512,
                                                 a.shape[-1]),
        interpret=INTERPRET if interpret is None else interpret)
    return z


def reorth_right(a, u, v_buf, *, expansion: int = 8,
                 interpret: Optional[bool] = None):
    interp = INTERPRET if interpret is None else interpret
    return lanczos_reorth.reorth_right(a, u, v_buf, expansion=expansion,
                                       interpret=interp)


def reorth_right_batched(a, u, v_buf, *, expansion: int = 8,
                         interpret: Optional[bool] = None):
    interp = INTERPRET if interpret is None else interpret
    return lanczos_reorth.reorth_right_batched(a, u, v_buf,
                                               expansion=expansion,
                                               interpret=interp)


def reorth_left_batched(a, v, u_buf, *, expansion: int = 8,
                        interpret: Optional[bool] = None):
    interp = INTERPRET if interpret is None else interpret
    return lanczos_reorth.reorth_left_batched(a, v, u_buf,
                                              expansion=expansion,
                                              interpret=interp)


def reorth_left(a, v, u_buf, *, expansion: int = 8,
                interpret: Optional[bool] = None):
    interp = INTERPRET if interpret is None else interpret
    return lanczos_reorth.reorth_left(a, v, u_buf, expansion=expansion,
                                      interpret=interp)


def lowrank_matmul(vt, w, *, expansion: int = 8,
                   n_block: Optional[int] = None,
                   interpret: Optional[bool] = None):
    """Vᵀ[k,H] @ W[H,N]; zero-pads the H reduction to a multiple of the
    expansion factor (exact — pad products are 0·0) and N to a multiple
    of 128 so the kernel's block-divisor clamp never collapses to tiny
    N-blocks on prime-ish widths (a vocab-sized N would otherwise run a
    pathological (N, f) grid)."""
    interp = INTERPRET if interpret is None else interpret
    vt, _ = _pad_to(vt, 1, expansion)
    w, _ = _pad_to(w, 0, expansion)
    w, n = _pad_to(w, 1, 128)
    out = _lrmm.lowrank_matmul(vt, w, expansion=expansion,
                               n_block=min(n_block or 512, w.shape[1]),
                               interpret=interp)
    return out[:, :n]


def outlier_stats(x, threshold, *, expansion: int = 8,
                  interpret: Optional[bool] = None):
    interp = INTERPRET if interpret is None else interpret
    return outlier_extract.outlier_stats(x, threshold, expansion=expansion,
                                         interpret=interp)


def dkv_attention_stats(inner, k_u, v_u, *, expansion: int = 8,
                        interpret: Optional[bool] = None):
    """Rank-space flash stats over an ARBITRARY-length time axis: U_k/U_v
    are zero-padded through the cached pad plan and the kernel masks rows
    at or beyond the true length out of the softmax exactly."""
    interp = INTERPRET if interpret is None else interpret
    k_u, t = _pad_to(k_u, 0, expansion)
    v_u, _ = _pad_to(v_u, 0, expansion)
    return _dkv.dkv_attention_stats(inner, k_u, v_u, expansion=expansion,
                                    interpret=interp, t_valid=t)


def dkv_attention_stats_paged(inner, k_u_pages, v_u_pages, page_ids, *,
                              t_valid: int,
                              interpret: Optional[bool] = None):
    """Paged twin of :func:`dkv_attention_stats`: U blocks are DMA'd by
    prefetched page id out of the pools (no contiguous stream), one grid
    step per block-table entry; bit-compatible with the contiguous kernel
    at ``expansion == len(page_ids)`` on the gathered rows."""
    interp = INTERPRET if interpret is None else interpret
    return _dkv.dkv_attention_stats_paged(inner, k_u_pages, v_u_pages,
                                          page_ids, t_valid=t_valid,
                                          interpret=interp)


merge_with_tail = _dkv.merge_with_tail


def ssd_chunk_intra(cb, l, dt, x, *, head_block: int = 4,
                    interpret: Optional[bool] = None):
    interp = INTERPRET if interpret is None else interpret
    return ssd_chunk.ssd_chunk_intra(cb, l, dt, x, head_block=head_block,
                                     interpret=interp)


# ---------------------------------------------------------------------------
# Lanczos hook factory: plugs the fused Pallas steps into core.lanczos
# ---------------------------------------------------------------------------

def make_pallas_hooks(expansion: int = 8,
                      interpret: Optional[bool] = None) -> LanczosHooks:
    """LanczosHooks whose inner steps run the fused D-com kernel.

    Shapes must divide by ``expansion`` (callers pad); normalization stays in
    ``core.lanczos`` (the kernels return unnormalized vectors; the returned
    ‖z‖² is dropped here because _safe_normalize recomputes it — O(H)).

    The returned hooks are cached per (expansion, RESOLVED interpret) so
    they keep a stable identity — they are static jit arguments in
    ``core.lanczos``, and fresh closures would retrace on every engine
    construction.  The module-level ``INTERPRET`` flag is re-read on every
    call (never baked into a cache key), so flipping it for TPU deployment
    keeps working.
    """
    return _make_pallas_hooks(expansion,
                              INTERPRET if interpret is None else interpret)


@functools.lru_cache(maxsize=None)
def _make_pallas_hooks(expansion: int, interp: bool) -> LanczosHooks:
    def right_step(a, u, v_buf):
        z, _ = lanczos_reorth.reorth_right(a, u, v_buf, expansion=expansion,
                                           interpret=interp)
        return z

    def left_step(a, v, u_buf):
        w, _ = lanczos_reorth.reorth_left(a, v, u_buf, expansion=expansion,
                                          interpret=interp)
        return w

    return LanczosHooks(right_step=right_step, left_step=left_step)


def make_batched_pallas_hooks(expansion: int = 8,
                              interpret: Optional[bool] = None
                              ) -> BatchedLanczosHooks:
    """BatchedLanczosHooks running ONE fused Pallas launch per Lanczos pass
    for the whole prompt batch (grid = (B, 3, f)) — no vmap over pallas_call.

    Shapes must divide by ``expansion`` on the reduced axis (the engine pads
    via the cached :func:`padded_dims` plan).  Cached per (expansion,
    resolved interpret) for stable jit identity, like
    :func:`make_pallas_hooks`; ``INTERPRET`` is re-read per call.
    """
    return _make_batched_pallas_hooks(
        expansion, INTERPRET if interpret is None else interpret)


@functools.lru_cache(maxsize=None)
def _make_batched_pallas_hooks(expansion: int, interp: bool
                               ) -> BatchedLanczosHooks:
    def right_step(a, u, v_buf):
        z, _ = lanczos_reorth.reorth_right_batched(
            a, u, v_buf, expansion=expansion, interpret=interp)
        return z

    def left_step(a, v, u_buf):
        w, _ = lanczos_reorth.reorth_left_batched(
            a, v, u_buf, expansion=expansion, interpret=interp)
        return w

    return BatchedLanczosHooks(right_step=right_step, left_step=left_step)


def make_vmapped_pallas_hooks(expansion: int = 8,
                              interpret: Optional[bool] = None
                              ) -> BatchedLanczosHooks:
    """vmap-of-scalar-kernel fallback hooks (the pre-engine batching scheme).

    Kept as an explicit backend so the engine benchmark can measure batched
    launch vs per-prompt vmap, and as the escape hatch for shapes a native
    batched launch cannot take.
    """
    return _make_vmapped_pallas_hooks(
        expansion, INTERPRET if interpret is None else interpret)


@functools.lru_cache(maxsize=None)
def _make_vmapped_pallas_hooks(expansion: int, interp: bool
                               ) -> BatchedLanczosHooks:
    from ..core.lanczos import batch_hooks
    return batch_hooks(_make_pallas_hooks(expansion, interp))
