"""Plain float32 reference of the served model, cache and factorization.

It imports nothing of the program.  It runs, for one request, what the
served path computes, written out directly in ``jax.numpy`` at float32 with
every matmul at ``Precision.HIGHEST``:

1. the prefill forward of the prompt as the server admits it (left-padded
   with token 0 to its scheduler bucket; positions count from the first
   pad) — a dense decoder: RMSNorm, RoPE on pairs ``(2i, 2i+1)``, causal
   grouped-query attention, gated SiLU MLP, tied or separate head;
2. each layer's K (after RoPE) and V factorized to rank ``r`` by
   Golub–Kahan–Lanczos bidiagonalization: ``r + extra`` steps from the
   start vector ``N(0, I)`` drawn with key 0, full re-orthogonalization by
   classical Gram–Schmidt applied twice, then the SVD of the small
   bidiagonal, keeping its ``r`` largest triplets;
3. decode of the served tokens, teacher-forced: each new token attends to
   the rank-``r`` reconstruction of the prompt's K/V and, exactly, to the
   tokens decoded before it and itself.

It runs layer by layer (one compiled program per layer kind and prompt
bucket), so it fits beside the weights.  ``control=True`` computes the
same in the next precision below the configuration's bfloat16: every
weight and the prompt's K/V round-trip through float8 (e4m3, absmax
scales per output column and per tensor).
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

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


_ACT = {"silu": jax.nn.silu}


def _dims(m):
    hd = m.get("head_dim") or m["d_model"] // m["num_heads"]
    return m["num_heads"], m["num_kv_heads"], hd


def _block(lp, x, pos, kv_extra, mask, m, control):
    """One decoder layer over rows ``x`` at positions ``pos``; keys and
    values are ``kv_extra`` (rows before these, or None) then these rows'
    own.  Returns (x, k, v) with k/v of these rows [S, kvh·hd]."""
    nh, kvh, hd = _dims(m)
    s = x.shape[0]
    h = _rmsnorm(x, lp["attn_norm"]["scale"], m["norm_eps"])
    a = lp["attn"]
    q = _rope(_mm(h, _w(a["wq"]["w"], control)).reshape(s, nh, hd), pos,
              m["rope_theta"])
    k = _rope(_mm(h, _w(a["wk"]["w"], control)).reshape(s, kvh, hd), pos,
              m["rope_theta"])
    v = _mm(h, _w(a["wv"]["w"], control)).reshape(s, kvh, hd)
    keys, vals = k, v
    if kv_extra is not None:
        kp, vp = kv_extra
        keys = jnp.concatenate([kp.reshape(-1, kvh, hd), k], 0)
        vals = jnp.concatenate([vp.reshape(-1, kvh, hd), v], 0)
    x = x + _mm(_attend(q, keys, vals, mask), _w(a["wo"]["w"], control))
    h = _rmsnorm(x, lp["mlp_norm"]["scale"], m["norm_eps"])
    mp = lp["mlp"]
    act = _ACT[m["activation"]]
    if "gate" in mp:
        hh = act(_mm(h, _w(mp["gate"]["w"], control))) \
            * _mm(h, _w(mp["up"]["w"], control))
    else:
        hh = act(_mm(h, _w(mp["up"]["w"], control)))
    x = x + _mm(hh, _w(mp["down"]["w"], control))
    return x, k.reshape(s, kvh * hd), v.reshape(s, kvh * hd)


def _layer(layers, idx):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False),
        layers)


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


def _embed(params, toks, m, control):
    e = params["embed"]["w"]
    rows = e[toks].astype(F32)
    if control:
        rows = fp8(rows, 1)
    scale = m["d_model"] ** 0.5 if m.get("tie_embeddings") else 1.0
    return rows * scale


def _head(params, x, m, control):
    x = _rmsnorm(x, params["final_norm"]["scale"], m["norm_eps"])
    if m.get("tie_embeddings"):
        e = params["embed"]["w"].astype(F32)
        w = (fp8(e, 1) if control else e).T
    else:
        w = _w(params["lm_head"]["w"], control)
    return _mm(x, w)[..., :m["vocab"]]


@functools.partial(jax.jit, static_argnames=("mt", "control", "rank",
                                             "iters"))
def _prefill_layer(layers, idx, x, *, mt, control, rank, iters):
    m = dict(mt)
    s = x.shape[0]
    pos = jnp.arange(s)
    mask = pos[:, None] >= pos[None, :]
    x, k, v = _block(_layer(layers, idx), x, pos, None, mask, m, control)
    if control:
        k, v = fp8(k), fp8(v)
    rec = []
    for a in (k, v):
        us, vt = lanczos(a, rank, iters)
        rec.append(_mm(us, vt))
    return x, rec[0], rec[1]


@functools.partial(jax.jit, static_argnames=("mt", "control"))
def _decode_layer(layers, idx, x, kp, vp, *, mt, control):
    m = dict(mt)
    n, p = x.shape[0], kp.shape[0]
    pos = p + jnp.arange(n)
    i = jnp.arange(n)
    mask = jnp.concatenate([jnp.ones((n, p), bool),
                            i[None, :] <= i[:, None]], axis=1)
    x, _, _ = _block(_layer(layers, idx), x, pos, (kp, vp), mask, m,
                     control)
    return x


@functools.partial(jax.jit, static_argnames=("mt", "control"))
def _embed_jit(params, toks, *, mt, control):
    return _embed(params, toks, dict(mt), control)


@functools.partial(jax.jit, static_argnames=("mt", "control"))
def _head_jit(params, x, *, mt, control):
    return _head(params, x, dict(mt), control)


def _mt(model: dict) -> Tuple:
    keys = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
            "vocab", "num_layers", "tie_embeddings", "norm_eps",
            "rope_theta", "activation", "gated_mlp")
    return tuple((k, model.get(k)) for k in keys)


def served_logits(params, model: dict, padded_prompt: np.ndarray,
                  served: Sequence[int], *, rank: int, iters: int,
                  decode_pad: int, control: bool = False) -> np.ndarray:
    """Reference logits [len(served), vocab] at every served position: row
    0 from the prompt's last position, row i from decoding served token
    i−1.  Decode inputs are padded to ``decode_pad`` tokens (causal, so the
    padding changes no earlier row) to keep one program per prompt
    bucket."""
    mt = _mt(model)
    layers = params["layers"]
    nl = model["num_layers"]
    with jax.default_matmul_precision("highest"):
        x = _embed_jit(params, jnp.asarray(padded_prompt, jnp.int32),
                       mt=mt, control=control)
        kv: List[Tuple] = []
        for idx in range(nl):
            x, kr, vr = _prefill_layer(layers, np.int32(idx), x, mt=mt,
                                       control=control, rank=rank,
                                       iters=iters)
            kv.append((kr, vr))
        first = _head_jit(params, x[-1:], mt=mt, control=control)
        n_dec = len(served) - 1
        rows = [np.asarray(first, np.float32)]
        if n_dec > 0:
            if n_dec > decode_pad:
                raise ValueError(f"{n_dec} decode steps > pad {decode_pad}")
            toks = np.zeros(decode_pad, np.int32)
            toks[:n_dec] = np.asarray(served[:-1], np.int32)
            xd = _embed_jit(params, jnp.asarray(toks), mt=mt,
                            control=control)
            for idx in range(nl):
                xd = _decode_layer(layers, np.int32(idx), xd, *kv[idx],
                                   mt=mt, control=control)
            dl = _head_jit(params, xd, mt=mt, control=control)
            rows.append(np.asarray(dl, np.float32)[:n_dec])
        del kv
    return np.concatenate(rows, 0)


def gaps(ref: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """How far each token's reference logit lies below the reference's best
    at its position."""
    t = np.asarray(tokens, np.int64)
    return ref.max(-1) - ref[np.arange(len(t)), t]
