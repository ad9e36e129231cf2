"""Lanczos bidiagonalization (Golub–Kahan) — paper Algorithm 1.

The paper chooses Lanczos over QR / divide-and-conquer because it converges
fastest at the small ranks (1–20) useful for activation compression, and it
works directly on A (no AᵀA).  The runtime is dominated by the two
re-orthogonalization steps in the inner loop (paper Fig. 3); those are the
ops the D-com accelerator — and our Pallas kernel — fuse and expand.

Implementation notes
--------------------
* Fixed iteration count ``iters`` (static) so the whole factorization jits
  and scans; early-exit (paper line 6) is replaced by a numerical guard that
  zeroes further directions once ‖z‖ falls below ε — the resulting singular
  values come out ≈0, which is equivalent to the break.
* Full re-orthogonalization, classical Gram–Schmidt applied twice (CGS2) —
  matches the paper's "orthogonalize against V/U" and is what their
  accelerator executes.  U/V buffers are zero-padded to [.., iters], so
  projecting against not-yet-filled columns is a no-op.
* Lanczos vectors, coefficients and accumulation are fp32 regardless of
  input dtype, matching the fp32-accumulate behaviour of MXU/MAC hardware.
  A reaches the hooks in the dtype it was given: the jnp reference hooks
  upcast it, and the Pallas kernel streams a bf16 A as it lies (an exact
  split product, ``kernels/lanczos_reorth.py``).  Factors come out fp32
  from :func:`decompose_f32`; :func:`decompose` casts them to x's dtype.
* ``matvec``/``rmatvec``/``reorth`` are pluggable so the Pallas kernels in
  ``repro.kernels`` can replace the jnp reference implementations.
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .lowrank import LowRank

Array = jax.Array
EPS = 1e-8


class LanczosHooks(NamedTuple):
    """Pluggable fused inner steps (jnp reference by default; Pallas kernels
    via ``repro.kernels.ops.make_pallas_hooks``).

    Each step fuses (matvec → CGS2 re-orthogonalization) — exactly the op
    sequence the D-com accelerator expands (paper Fig. 9).  Normalization
    stays outside (O(S) / O(H), negligible).  Passing an all-zero Q buffer
    makes the re-orthogonalization a no-op (used for the first iteration).
    """
    right_step: Callable[[Array, Array, Array], Array]  # (A, u[S], V[H,k]) -> z[H]
    left_step: Callable[[Array, Array, Array], Array]   # (A, v[H], U[S,k]) -> u[S]


class BatchedLanczosHooks(NamedTuple):
    """Batched variant of :class:`LanczosHooks` — one call covers the whole
    prompt batch, so a Pallas backend launches ONE fused kernel per Lanczos
    pass (batch axis in the grid) instead of vmap-of-scalar-kernel per
    prompt.  ``repro.kernels.ops.make_batched_pallas_hooks`` builds the
    kernel-backed instance; :func:`batch_hooks` lifts any scalar hooks via
    ``jax.vmap`` (the compatibility fallback).
    """
    right_step: Callable[[Array, Array, Array], Array]  # (A[B,S,H], u[B,S], V[B,H,k]) -> z[B,H]
    left_step: Callable[[Array, Array, Array], Array]   # (A[B,S,H], v[B,H], U[B,S,k]) -> u[B,S]


def _reorth_cgs2(z: Array, q: Array) -> Array:
    """Twice-is-enough classical Gram–Schmidt: z ← z − Q(Qᵀz), twice."""
    z = z - q @ (q.T @ z)
    z = z - q @ (q.T @ z)
    return z


def _reorth_cgs2_batched(z: Array, q: Array) -> Array:
    """Batched CGS2: z [B, N], q [B, N, k] → z − Q(Qᵀz), twice."""
    for _ in range(2):
        p = jnp.einsum("bnk,bn->bk", q, z)
        z = z - jnp.einsum("bnk,bk->bn", q, p)
    return z


def _f32(a: Array) -> Array:
    return a.astype(jnp.float32)


DEFAULT_HOOKS = LanczosHooks(
    right_step=lambda a, u, vbuf: _reorth_cgs2(_f32(a).T @ u, vbuf),
    left_step=lambda a, v, ubuf: _reorth_cgs2(_f32(a) @ v, ubuf),
)

DEFAULT_BATCHED_HOOKS = BatchedLanczosHooks(
    right_step=lambda a, u, vbuf: _reorth_cgs2_batched(
        jnp.einsum("bsh,bs->bh", _f32(a), u), vbuf),
    left_step=lambda a, v, ubuf: _reorth_cgs2_batched(
        jnp.einsum("bsh,bh->bs", _f32(a), v), ubuf),
)


@lru_cache(maxsize=64)
def batch_hooks(hooks: LanczosHooks) -> BatchedLanczosHooks:
    """Lift scalar hooks to the batched protocol via ``jax.vmap``.

    This is the compatibility fallback (one kernel trace per prompt under
    vmap); native batched backends skip it entirely.  Cached per scalar
    hooks so the lifted functions hash stably as static jit arguments —
    BOUNDED, because callers may construct hooks from fresh closures and an
    unbounded cache would pin them for the process lifetime.
    """
    return BatchedLanczosHooks(right_step=jax.vmap(hooks.right_step),
                               left_step=jax.vmap(hooks.left_step))


class BidiagResult(NamedTuple):
    u: Array       # [S, k] left Lanczos vectors
    v: Array       # [H, k] right Lanczos vectors
    alpha: Array   # [k]   diagonal of B
    beta: Array    # [k-1] superdiagonal of B


def _safe_normalize(x: Array):
    n = jnp.linalg.norm(x)
    ok = n > EPS
    inv = jnp.where(ok, 1.0 / jnp.maximum(n, EPS), 0.0)
    return x * inv, jnp.where(ok, n, 0.0)


def _safe_normalize_batched(x: Array):
    """Row-wise safe normalize: x [B, N] → (unit rows, norms [B])."""
    n = jnp.linalg.norm(x, axis=-1)
    ok = n > EPS
    inv = jnp.where(ok, 1.0 / jnp.maximum(n, EPS), 0.0)
    return x * inv[:, None], jnp.where(ok, n, 0.0)


@partial(jax.jit, static_argnames=("iters", "hooks"))
def lanczos_bidiag(a: Array, iters: int,
                   z0: Optional[Array] = None,
                   hooks: LanczosHooks = DEFAULT_HOOKS) -> BidiagResult:
    """Golub–Kahan bidiagonalization of ``a [S, H]`` with ``iters`` steps.

    Produces A ≈ U B Vᵀ with B upper-bidiagonal (diag=alpha, superdiag=beta).
    The scalar path IS the B=1 slice of :func:`lanczos_bidiag_batched` —
    there is exactly one copy of the iteration math in this module.
    """
    res = lanczos_bidiag_batched(a[None], iters, z0=z0,
                                 hooks=batch_hooks(hooks))
    return BidiagResult(res.u[0], res.v[0], res.alpha[0], res.beta[0])


def bidiag_to_svd(res: BidiagResult, rank: int):
    """SVD of the tiny k×k bidiagonal B; rotate the Lanczos bases.

    Returns (U [S, rank], s [rank], Vt [rank, H]).
    """
    u, s, vt = bidiag_to_svd_batched(
        BidiagResult(res.u[None], res.v[None], res.alpha[None],
                     res.beta[None]), rank)
    return u[0], s[0], vt[0]


@partial(jax.jit, static_argnames=("rank", "iters", "hooks"))
def lanczos_svd(a: Array, rank: int, iters: Optional[int] = None,
                z0: Optional[Array] = None,
                hooks: LanczosHooks = DEFAULT_HOOKS):
    """Truncated SVD of a single matrix [S, H] via Lanczos bidiag.

    ``iters`` defaults to ``rank`` (paper-faithful: K iterations for rank K);
    oversampling (iters > rank) improves the trailing singular triplets.
    """
    iters = rank if iters is None else iters
    assert iters >= rank, "need at least `rank` Lanczos iterations"
    res = lanczos_bidiag(a, iters, z0=z0, hooks=hooks)
    return bidiag_to_svd(res, rank)


# ---------------------------------------------------------------------------
# Natively batched pipeline — one fused step per Lanczos pass for the WHOLE
# prompt batch (the batch axis lives in the hook / Pallas grid, never in a
# Python-level vmap over pallas_call).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("iters", "hooks"))
def lanczos_bidiag_batched(a: Array, iters: int,
                           z0: Optional[Array] = None,
                           hooks: BatchedLanczosHooks = DEFAULT_BATCHED_HOOKS
                           ) -> BidiagResult:
    """Golub–Kahan bidiagonalization of a batch ``a [B, S, H]``.

    Identical math to :func:`lanczos_bidiag` per batch element (same start
    vector when ``z0`` is None), but every inner step is ONE batched hook
    call, so kernel backends see the full batch per pass.  ``z0`` may be
    [H] (broadcast over the batch) or [B, H].  ``a`` goes to the hooks in
    its own dtype (a bf16 A is never copied to f32 here).
    """
    b_dim, s_dim, h_dim = a.shape
    if z0 is None:
        key = jax.random.PRNGKey(0)
        z0 = jax.random.normal(key, (h_dim,), jnp.float32)
    z0 = jnp.broadcast_to(z0.astype(jnp.float32), (b_dim, h_dim))

    u_buf = jnp.zeros((b_dim, s_dim, iters), jnp.float32)
    v_buf = jnp.zeros((b_dim, h_dim, iters), jnp.float32)
    alpha = jnp.zeros((b_dim, iters), jnp.float32)
    beta = jnp.zeros((b_dim, max(iters - 1, 1)), jnp.float32)

    v0, _ = _safe_normalize_batched(z0)
    u0 = hooks.left_step(a, v0, u_buf)   # U buffer all-zero ⇒ pure matvec
    u0, a0 = _safe_normalize_batched(u0)
    u_buf = u_buf.at[..., 0].set(u0)
    v_buf = v_buf.at[..., 0].set(v0)
    alpha = alpha.at[..., 0].set(a0)

    def body(j, carry):
        u_buf, v_buf, alpha, beta = carry
        u_prev = u_buf[..., j - 1]
        z = hooks.right_step(a, u_prev, v_buf)
        z, b = _safe_normalize_batched(z)
        v_buf = v_buf.at[..., j].set(z)
        beta = beta.at[..., j - 1].set(b)
        u = hooks.left_step(a, z, u_buf)
        u, al = _safe_normalize_batched(u)
        u_buf = u_buf.at[..., j].set(u)
        alpha = alpha.at[..., j].set(al)
        return u_buf, v_buf, alpha, beta

    u_buf, v_buf, alpha, beta = jax.lax.fori_loop(
        1, iters, body, (u_buf, v_buf, alpha, beta))
    return BidiagResult(u_buf, v_buf, alpha, beta)


def bidiag_to_svd_batched(res: BidiagResult, rank: int):
    """Batched SVD of the tiny k×k bidiagonal B; rotate the Lanczos bases.

    Returns (U [B, S, rank], s [B, rank], Vt [B, rank, H]).
    """
    k = res.alpha.shape[-1]
    b = jax.vmap(jnp.diag)(res.alpha)
    if k > 1:
        b = b + jax.vmap(partial(jnp.diag, k=1))(res.beta[..., :k - 1])
    p, s, qt = jnp.linalg.svd(b)
    u = jnp.einsum("bsk,bkr->bsr", res.u, p[..., :, :rank])
    vt = jnp.einsum("brk,bhk->brh", qt[..., :rank, :], res.v)
    return u, s[..., :rank], vt


@partial(jax.jit, static_argnames=("rank", "iters", "hooks", "batched_hooks"))
def decompose(x: Array, rank: int, iters: Optional[int] = None,
              hooks: Optional[LanczosHooks] = None,
              batched_hooks: Optional[BatchedLanczosHooks] = None,
              z0: Optional[Array] = None) -> LowRank:
    """Batched activation decomposition: x [..., S, H] → LowRank in x's
    dtype (the factors of :func:`decompose_f32`, cast).

    Each prompt's [S, H] slice is decomposed independently (paper §3.1:
    "we apply the decomposition on each prompt separately"), but the whole
    batch runs through ONE natively batched Lanczos pipeline: a kernel
    backend (``batched_hooks``) sees one fused launch per pass.  Scalar
    ``hooks`` are still accepted and lifted via :func:`batch_hooks` (the
    vmap fallback).  Prefer constructing a ``repro.engine.DecomposeEngine``,
    which also handles padding, outlier tracks, and backend selection.
    """
    if batched_hooks is None:
        batched_hooks = (DEFAULT_BATCHED_HOOKS if hooks is None
                         else batch_hooks(hooks))
    return decompose_f32(x, rank, iters=iters, batched_hooks=batched_hooks,
                         z0=z0).astype(x.dtype)


@partial(jax.jit, static_argnames=("rank", "iters", "batched_hooks"))
def decompose_f32(x: Array, rank: int, iters: Optional[int] = None,
                  batched_hooks: BatchedLanczosHooks = DEFAULT_BATCHED_HOOKS,
                  z0: Optional[Array] = None) -> LowRank:
    """:func:`decompose` with the factors left in fp32 whatever x's dtype,
    for callers that combine them before they round (``U·Σ`` in
    ``DecomposeEngine.decompose_kv``)."""
    iters = rank if iters is None else iters
    assert iters >= rank, "need at least `rank` Lanczos iterations"
    batch_shape = x.shape[:-2]
    flat = x.reshape((-1,) + x.shape[-2:])
    res = lanczos_bidiag_batched(flat, iters, z0=z0, hooks=batched_hooks)
    u, s, vt = bidiag_to_svd_batched(res, rank)
    u = u.reshape(batch_shape + u.shape[1:])
    s = s.reshape(batch_shape + s.shape[1:])
    vt = vt.reshape(batch_shape + vt.shape[1:])
    return LowRank(u, s, vt)
