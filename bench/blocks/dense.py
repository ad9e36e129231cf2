"""The dense decoder block: weights layout, plain reference and counted
work (the contract is in ``bench/blocks/__init__.py``).

A dense decoder as the serving engine takes it: RMSNorm, RoPE on pairs
``(2i, 2i+1)``, causal grouped-query attention, a gated SiLU (or plain)
MLP, a tied or separate head, per-layer weights stacked on a leading
layer axis.  Every layer's K and V is factorized.  The reference runs,
for one request:

1. the prefill forward of the prompt as the server admits it (left-padded
   with token 0 to its scheduler bucket; positions count from the first
   pad);
2. each layer's K (after RoPE) and V factorized to rank ``r`` by
   ``reference.lanczos``;
3. decode of the served tokens, teacher-forced: each new token attends to
   the rank-``r`` reconstruction of the prompt's K/V and, exactly, to the
   tokens decoded before it and itself.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import (F32, _attend, _mm, _rmsnorm, _rope, _w, fp8,
                             lanczos)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def layout(model: dict) -> Dict[str, Any]:
    """Tree of ``(shape, scale)``; scale ``None`` is a norm gain of ones,
    otherwise a normal draw times ``scale`` (1/√fan_in for matrices)."""
    if model.get("use_bias"):
        raise ValueError("the benchmark's weights cover bias-free models")
    nl, d, nh, kvh = (model["num_layers"], model["d_model"],
                      model["num_heads"], model["num_kv_heads"])
    hd = model.get("head_dim") or d // nh
    ff = model["d_ff"]
    vp = (model["vocab"] + 127) // 128 * 128
    lin = lambda i, o: ((nl, i, o), i ** -0.5)
    mlp = {"up": {"w": lin(d, ff)}, "down": {"w": lin(ff, d)}}
    if model.get("gated_mlp", True):
        mlp["gate"] = {"w": lin(d, ff)}
    # A tied table is drawn with std 1/d: the model multiplies input rows
    # by sqrt(d), so they then have the std 1/sqrt(d) of an untied
    # model's.  Drawn at 1/sqrt(d), the tied head would score the current
    # token about sqrt(d) standard deviations above the rest, and greedy
    # decoding would repeat the last prompt token forever.
    tied = model.get("tie_embeddings", False)
    tree = {
        "embed": {"w": ((vp, d), 1.0 / d if tied else d ** -0.5)},
        "layers": {
            "attn_norm": {"scale": ((nl, d), None)},
            "attn": {"wq": {"w": lin(d, nh * hd)},
                     "wk": {"w": lin(d, kvh * hd)},
                     "wv": {"w": lin(d, kvh * hd)},
                     "wo": {"w": lin(nh * hd, d)}},
            "mlp_norm": {"scale": ((nl, d), None)},
            "mlp": mlp,
        },
        "final_norm": {"scale": ((d,), None)},
    }
    if not tied:
        tree["lm_head"] = {"w": ((d, vp), d ** -0.5)}
    return tree


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

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
    bucket.  It runs layer by layer (one compiled program per layer kind
    and prompt bucket), so it fits beside the weights."""
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


# ---------------------------------------------------------------------------
# counted work
# ---------------------------------------------------------------------------

def _sizes(model: dict) -> Tuple[int, int, int, int, int, int, int]:
    d, nh, kvh = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // nh
    return (model["num_layers"], d, nh, kvh, hd, model["d_ff"],
            model["vocab"])


def forward_flops(model: dict, prompt_len: int) -> float:
    """FLOPs of one prompt's prefill forward: every projection and MLP
    matmul for each prompt token, causal attention (scores and values over
    the ``s·(s+1)/2`` visible pairs), and the head for the one position
    whose logits are sampled."""
    nl, d, nh, kvh, hd, ff, vocab = _sizes(model)
    s = int(prompt_len)
    mlp_mats = 3 if model.get("gated_mlp", True) else 2
    per_token = 2 * (d * nh * hd + 2 * d * kvh * hd + nh * hd * d
                     + mlp_mats * d * ff)
    attn = 2 * 2 * nh * hd * s * (s + 1) / 2
    return nl * (per_token * s + attn) + 2 * d * vocab


def reorth_needed(model: dict, prompt_len: int, rank: int,
                  iters_extra: int, a_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) the Lanczos re-orthogonalization of one prompt's K
    and V needs, in every layer.

    Each of ``k = rank + iters_extra`` steps makes two matvecs (Aᵀu, then
    Av), each reading the ``s × kvw`` activation once at ``a_bytes`` per
    element (the dtype prefill wrote), and projects against the Lanczos
    basis built so far (``j`` columns of float32 at step ``j``) with
    classical Gram–Schmidt applied twice."""
    nl, _, _, kvh, hd, _, _ = _sizes(model)
    s, h = int(prompt_len), kvh * hd
    k = int(rank) + int(iters_extra)
    basis_cols = k * (k - 1) / 2                 # sum of j over the steps
    by = 2 * k * s * h * a_bytes + (s + h) * 4 * basis_cols
    fl = 2 * k * 2 * s * h + 2 * 2 * 2 * (s + h) * basis_cols
    return 2 * nl * fl, 2 * nl * by
