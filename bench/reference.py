"""Plain float32 reference toolkit: the parts any block's reference uses.

It imports nothing of the program.  Each block (``bench/blocks/<name>.py``)
writes its own model out of these in ``jax.numpy`` at float32 with every
matmul at ``Precision.HIGHEST``, in its ``served_logits``; the dense
decoder's is ``bench/blocks/dense.py``.  Here:

* RMSNorm, RoPE on pairs ``(2i, 2i+1)`` and grouped-query attention under
  a mask;
* the factorization of a K or V to rank ``r`` by Golub–Kahan–Lanczos
  bidiagonalization: ``r + extra`` steps from the start vector
  ``N(0, I)`` drawn with key 0, full re-orthogonalization by classical
  Gram–Schmidt applied twice, then the SVD of the small bidiagonal,
  keeping its ``r`` largest triplets;
* the control's rounding: with ``control=True`` a block computes the same
  in the next precision below the configuration's bfloat16, every weight
  and the prompt's K/V round-tripped through float8 (e4m3, absmax scales
  per output column and per tensor);
* ``gaps``, the number the output check compares.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
EPS = 1e-8
FP8_MAX = 448.0


def fp8(x, axis=None):
    """Round ``x`` through float8 e4m3 with an absmax scale over ``axis``
    (``None``: the whole tensor)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _w(w, control):
    w = w.astype(F32)
    return fp8(w, 0) if control else w


def _mm(x, w):
    return jnp.matmul(x, w, precision=HI)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def _rope(x, pos, theta):
    """x [S, n, hd], pos [S]: rotate each pair (2i, 2i+1) by pos·θ^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attend(q, k, v, mask):
    """q [S, nh, hd], k/v [T, kvh, hd], mask [S, T] → [S, nh·hd]."""
    s, nh, hd = q.shape
    kvh = k.shape[1]
    qg = q.reshape(s, kvh, nh // kvh, hd)
    sc = jnp.einsum("skgd,tkd->kgst", qg, k, precision=HI) * hd ** -0.5
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("kgst,tkd->skgd", p, v, precision=HI)
    return o.reshape(s, nh * hd)


def _normalize(x):
    n = jnp.linalg.norm(x)
    ok = n > EPS
    return x * jnp.where(ok, 1.0 / jnp.maximum(n, EPS), 0.0), \
        jnp.where(ok, n, 0.0)


def _cgs2(z, q):
    for _ in range(2):
        z = z - _mm(q, _mm(q.T, z))
    return z


def lanczos(a, rank: int, iters: int):
    """Rank-``rank`` factors (U·Σ [S, r], Vᵀ [r, H]) of ``a`` [S, H] by
    ``iters`` Golub–Kahan–Lanczos steps with full re-orthogonalization."""
    s, h = a.shape
    z0 = jax.random.normal(jax.random.PRNGKey(0), (h,), F32)
    v, _ = _normalize(z0)
    u, al = _normalize(_mm(a, v))
    ub = jnp.zeros((s, iters), F32).at[:, 0].set(u)
    vb = jnp.zeros((h, iters), F32).at[:, 0].set(v)
    alpha = jnp.zeros((iters,), F32).at[0].set(al)
    beta = jnp.zeros((max(iters - 1, 1),), F32)

    def step(j, c):
        ub, vb, alpha, beta = c
        z = _cgs2(_mm(a.T, ub[:, j - 1]), vb)
        v, b = _normalize(z)
        w = _cgs2(_mm(a, v), ub)
        u, al = _normalize(w)
        return (ub.at[:, j].set(u), vb.at[:, j].set(v), alpha.at[j].set(al),
                beta.at[j - 1].set(b))

    ub, vb, alpha, beta = jax.lax.fori_loop(1, iters, step,
                                            (ub, vb, alpha, beta))
    bd = jnp.diag(alpha) + jnp.diag(beta[:iters - 1], 1)
    p, sv, qt = jnp.linalg.svd(bd)
    us = _mm(ub, p[:, :rank]) * sv[:rank]
    vt = _mm(qt[:rank], vb.T)
    return us, vt


def gaps(ref: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """How far each token's reference logit lies below the reference's best
    at its position."""
    t = np.asarray(tokens, np.int64)
    return ref.max(-1) - ref[np.arange(len(t)), t]
