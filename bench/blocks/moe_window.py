"""The window/full expert decoder block (Mellum2-12B-A2.5B): weights layout,
plain reference and counted work (the contract is in
``bench/blocks/__init__.py``).

The layer equations, as the configuration gives them, for layer ``l`` of
kind ``window`` or ``full`` (layer ``l`` is full iff
``(l + 1) % full_attn_every == 0``):

* ``h = RMSNorm(x)`` (ε ``norm_eps``, gain ``attn_norm``);
* ``q = h·Wq``, ``k = h·Wk``, ``v = h·Wv``, grouped-query attention with
  ``num_heads`` query and ``num_kv_heads`` key/value heads of
  ``head_dim``; RoPE rotates each pair ``(2i, 2i+1)`` of q and k by
  ``p·f_i``, and multiplies cos and sin by ``s``.  Window layers: plain
  ``f_i = θ^(−2i/d)``, ``s = 1``.  Full layers: YaRN over
  ``yarn_original_max_pos`` positions — pairs that turn more than
  ``yarn_beta_fast`` times keep ``θ^(−2i/d)``, pairs that turn fewer than
  ``yarn_beta_slow`` times take ``θ^(−2i/d) / yarn_factor``, a linear ramp
  between (bounds floored and ceiled) blends them, and
  ``s = yarn_attention_factor``;
* causal softmax attention at scale ``head_dim^−½``; in a window layer key
  j is visible to query i iff ``0 ≤ i − j < sliding_window``;
  ``x += attn·Wo``;
* ``h = RMSNorm(x)`` (gain ``mlp_norm``); router ``softmax(h·Wr)`` over all
  ``router_experts``; the ``top_k`` largest, their gates renormalized to
  sum to one; each held expert ``e`` (``expert_first`` …
  ``expert_first + num_experts − 1``) that a token picks adds
  ``g_e · (SiLU(h·Wg_e) ⊙ h·Wu_e)·Wd_e``; the picks of experts held
  elsewhere add nothing here, as in the program (the chip's share);
  ``x += y``;
* after the last layer ``RMSNorm`` and the untied head.

The reference runs, for one request, at float32 with every matmul at
``Precision.HIGHEST``:

1. the prefill forward of the prompt as the server admits it (left-padded
   with token 0 to its bucket; positions count from the first pad);
2. each full layer's K (after RoPE) and V factorized to rank ``r`` by
   ``reference.lanczos``; the window layers' K and V are kept exactly;
3. decode of the served tokens, teacher-forced: in a full layer each new
   token attends to the rank-``r`` reconstruction of the prompt's K/V and
   exactly to the tokens decoded before it and itself; in a window layer
   to the exact rows inside its window (the last ``sliding_window`` rows).

It runs layer by layer (one compiled program per layer kind and prompt
bucket) and computes the held experts densely over the rows, so that it
fits beside the weights on one chip.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import F32, _attend, _mm, _rmsnorm, _w, fp8, lanczos


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _dims(m):
    d, nh = m["d_model"], m["num_heads"]
    return nh, m["num_kv_heads"], m.get("head_dim") or d // nh


def layout(model: dict) -> Dict[str, Any]:
    """Tree of ``(shape, scale)``: per-layer attention stacks, a router of
    the published width, the held experts' stacks ``[L, held, …]`` and an
    untied head; scale ``None`` is a norm gain of ones, else a normal draw
    times ``scale`` (1/√fan_in)."""
    if model.get("use_bias") or model.get("tie_embeddings"):
        raise ValueError("the block is bias-free with an untied head")
    nl, d = model["num_layers"], model["d_model"]
    nh, kvh, hd = _dims(model)
    held, f = model["num_experts"], model["moe_d_ff"]
    vp = (model["vocab"] + 127) // 128 * 128
    lin = lambda i, o: ((nl, i, o), i ** -0.5)
    return {
        "embed": {"w": ((vp, d), d ** -0.5)},
        "layers": {
            "attn_norm": {"scale": ((nl, d), None)},
            "attn": {"wq": {"w": lin(d, nh * hd)},
                     "wk": {"w": lin(d, kvh * hd)},
                     "wv": {"w": lin(d, kvh * hd)},
                     "wo": {"w": lin(nh * hd, d)}},
            "mlp_norm": {"scale": ((nl, d), None)},
            "moe": {"router": {"w": lin(d, model["router_experts"])},
                    "w_gate": ((nl, held, d, f), d ** -0.5),
                    "w_up": ((nl, held, d, f), d ** -0.5),
                    "w_down": ((nl, held, f, d), f ** -0.5)},
        },
        "final_norm": {"scale": ((d,), None)},
        "lm_head": {"w": ((d, vp), d ** -0.5)},
    }


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def kind_of(m: dict, layer: int) -> str:
    n = m.get("full_attn_every") or 0
    if not m.get("sliding_window"):
        return "full"
    return "full" if n and (layer + 1) % n == 0 else "window"


def rope_table(m: dict, kind: str) -> Tuple[np.ndarray, float]:
    """(per-pair frequencies [hd/2], cos/sin scale) of a layer kind."""
    hd = _dims(m)[2]
    theta = float(m["rope_theta"])
    base = theta ** -(np.arange(0, hd, 2, dtype=np.float64) / hd)
    if kind == "window" or not m.get("yarn_factor"):
        return base.astype(np.float32), 1.0
    orig = float(m["yarn_original_max_pos"])

    def dim_at(turns):
        return hd * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim_at(m["yarn_beta_fast"])), 0)
    hi = min(math.ceil(dim_at(m["yarn_beta_slow"])), hd - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = np.clip((np.arange(hd // 2) - lo) / (hi - lo), 0.0, 1.0)
    freqs = base / float(m["yarn_factor"]) * ramp + base * (1.0 - ramp)
    return freqs.astype(np.float32), float(m["yarn_attention_factor"])


def _rope(x, pos, table):
    """x [S, n, hd] rotated on pairs (2i, 2i+1) by pos·f_i, times scale."""
    freqs, scale = table
    ang = pos.astype(F32)[:, None] * jnp.asarray(freqs)
    cos = jnp.cos(ang)[:, None, :] * scale
    sin = jnp.sin(ang)[:, None, :] * scale
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _experts(mp, h, m, control):
    """The held experts' share of the expert layer over rows h [S, d]."""
    router = _w(mp["router"]["w"], control)
    probs = jax.nn.softmax(_mm(h, router), axis=-1)          # [S, E]
    top, idx = jax.lax.top_k(probs, m["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    first, held = m.get("expert_first", 0), m["num_experts"]
    gates = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)       # [S, E]
    gates = gates[:, first:first + held]                     # [S, held]
    wq = (lambda w: fp8(w.astype(F32), 1)) if control \
        else (lambda w: w.astype(F32))
    wg, wu, wd = wq(mp["w_gate"]), wq(mp["w_up"]), wq(mp["w_down"])
    hp = jax.lax.Precision.HIGHEST
    a = jax.nn.silu(jnp.einsum("sd,edf->esf", h, wg, precision=hp)) \
        * jnp.einsum("sd,edf->esf", h, wu, precision=hp)
    out = jnp.einsum("esf,efd->esd", a, wd, precision=hp)   # [held, S, d]
    return jnp.einsum("se,esd->sd", gates, out, precision=hp)


def _block(lp, x, pos, kv_extra, mask, m, kind, control):
    """One layer over rows ``x`` at positions ``pos``; keys and values are
    ``kv_extra`` (rows before these, or None) then these rows' own.
    Returns (x, k, v) with k/v of these rows [S, kvh·hd]."""
    nh, kvh, hd = _dims(m)
    s = x.shape[0]
    table = rope_table(m, kind)
    h = _rmsnorm(x, lp["attn_norm"]["scale"], m["norm_eps"])
    a = lp["attn"]
    q = _rope(_mm(h, _w(a["wq"]["w"], control)).reshape(s, nh, hd), pos,
              table)
    k = _rope(_mm(h, _w(a["wk"]["w"], control)).reshape(s, kvh, hd), pos,
              table)
    v = _mm(h, _w(a["wv"]["w"], control)).reshape(s, kvh, hd)
    keys, vals = k, v
    if kv_extra is not None:
        kp, vp = kv_extra
        keys = jnp.concatenate([kp.reshape(-1, kvh, hd), k], 0)
        vals = jnp.concatenate([vp.reshape(-1, kvh, hd), v], 0)
    x = x + _mm(_attend(q, keys, vals, mask), _w(a["wo"]["w"], control))
    h = _rmsnorm(x, lp["mlp_norm"]["scale"], m["norm_eps"])
    x = x + _experts(lp["moe"], h, m, control)
    return x, k.reshape(s, kvh * hd), v.reshape(s, kvh * hd)


def _layer(layers, idx):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, idx, 0, keepdims=False),
        layers)


def _window_mask(qpos, kpos, m, kind):
    diff = qpos[:, None] - kpos[None, :]
    mask = diff >= 0
    if kind == "window":
        mask = mask & (diff < m["sliding_window"])
    return mask


@functools.partial(jax.jit, static_argnames=("mt", "kind", "control",
                                             "rank", "iters"))
def _prefill_layer(layers, idx, x, *, mt, kind, control, rank, iters):
    m = dict(mt)
    pos = jnp.arange(x.shape[0])
    x, k, v = _block(_layer(layers, idx), x, pos, None,
                     _window_mask(pos, pos, m, kind), m, kind, control)
    if control:
        k, v = fp8(k), fp8(v)
    if kind == "window":
        return x, k, v
    rec = []
    for a in (k, v):
        us, vt = lanczos(a, rank, iters)
        rec.append(_mm(us, vt))
    return x, rec[0], rec[1]


@functools.partial(jax.jit, static_argnames=("mt", "kind", "control"))
def _decode_layer(layers, idx, x, kp, vp, *, mt, kind, control):
    m = dict(mt)
    n, p = x.shape[0], kp.shape[0]
    qpos = p + jnp.arange(n)
    kpos = jnp.arange(p + n)
    x, _, _ = _block(_layer(layers, idx), x, qpos, (kp, vp),
                     _window_mask(qpos, kpos, m, kind), m, kind, control)
    return x


@functools.partial(jax.jit, static_argnames=("mt", "control"))
def _embed_jit(params, toks, *, mt, control):
    rows = params["embed"]["w"][toks].astype(F32)
    return fp8(rows, 1) if control else rows


@functools.partial(jax.jit, static_argnames=("mt", "control"))
def _head_jit(params, x, *, mt, control):
    m = dict(mt)
    x = _rmsnorm(x, params["final_norm"]["scale"], m["norm_eps"])
    return _mm(x, _w(params["lm_head"]["w"], control))[..., :m["vocab"]]


_KEYS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "vocab",
         "num_layers", "norm_eps", "rope_theta", "sliding_window",
         "full_attn_every", "yarn_factor", "yarn_original_max_pos",
         "yarn_beta_fast", "yarn_beta_slow", "yarn_attention_factor",
         "num_experts", "router_experts", "expert_first", "top_k",
         "moe_d_ff")


def _mt(model: dict) -> Tuple:
    return tuple((k, model.get(k)) for k in _KEYS)


def served_logits(params, model: dict, padded_prompt: np.ndarray,
                  served: Sequence[int], *, rank: int, iters: int,
                  decode_pad: int, control: bool = False) -> np.ndarray:
    """Reference logits [len(served), vocab] at every served position: row
    0 from the prompt's last position, row i from decoding served token
    i−1.  Decode inputs are padded to ``decode_pad`` tokens (causal, so the
    padding changes no earlier row)."""
    mt = _mt(model)
    layers = params["layers"]
    nl = model["num_layers"]
    with jax.default_matmul_precision("highest"):
        x = _embed_jit(params, jnp.asarray(padded_prompt, jnp.int32),
                       mt=mt, control=control)
        kv: List[Tuple] = []
        for idx in range(nl):
            x, kr, vr = _prefill_layer(layers, np.int32(idx), x, mt=mt,
                                       kind=kind_of(model, idx),
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
                                   mt=mt, kind=kind_of(model, idx),
                                   control=control)
            dl = _head_jit(params, xd, mt=mt, control=control)
            rows.append(np.asarray(dl, np.float32)[:n_dec])
        del kv
    return np.concatenate(rows, 0)


# ---------------------------------------------------------------------------
# counted work
# ---------------------------------------------------------------------------

def _kinds(model: dict) -> List[str]:
    return [kind_of(model, i) for i in range(model["num_layers"])]


def _held_share(model: dict) -> float:
    """Expected expert evaluations a token makes here: its top-k picks
    times the share of the router's experts that this chip holds."""
    return model["top_k"] * model["num_experts"] / model["router_experts"]


def forward_flops(model: dict, prompt_len: int) -> float:
    """FLOPs of one prompt's prefill forward: per prompt token and layer
    the projections, the router, and the held experts at the expected
    routed share (``top_k × held / router width`` expert evaluations a
    token: what this chip computes under uniform routing); causal
    attention over the visible pairs (a full layer's ``s·(s+1)/2``, a
    window layer's rows within its window); the head for the one sampled
    position."""
    nh, kvh, hd = _dims(model)
    d, f, s = model["d_model"], model["moe_d_ff"], int(prompt_len)
    per_token = 2 * (d * nh * hd + 2 * d * kvh * hd + nh * hd * d
                     + d * model["router_experts"]
                     + _held_share(model) * 3 * d * f)
    w = model.get("sliding_window") or s
    pairs = {"full": s * (s + 1) / 2,
             "window": sum(min(i + 1, w) for i in range(s))}
    attn = sum(2 * 2 * nh * hd * pairs[k] for k in _kinds(model))
    return model["num_layers"] * per_token * s + attn \
        + 2 * d * model["vocab"]


def reorth_needed(model: dict, prompt_len: int, rank: int,
                  iters_extra: int, a_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) the Lanczos re-orthogonalization of one prompt's K
    and V needs in the full layers, the only ones factorized: each of
    ``k = rank + iters_extra`` steps makes two matvecs that each read the
    ``s × kvw`` activation once at ``a_bytes`` per element, and projects
    against the basis built so far by classical Gram–Schmidt applied
    twice (as ``dense.reorth_needed`` counts a layer)."""
    _, kvh, hd = _dims(model)
    n_full = _kinds(model).count("full")
    s, h = int(prompt_len), kvh * hd
    k = int(rank) + int(iters_extra)
    basis_cols = k * (k - 1) / 2
    by = 2 * k * s * h * a_bytes + (s + h) * 4 * basis_cols
    fl = 2 * k * 2 * s * h + 2 * 2 * 2 * (s + h) * basis_cols
    return 2 * n_full * fl, 2 * n_full * by


def expert_needed(model: dict, prompt_len: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of the held experts in one prompt's admission, in
    every layer: the gate, up and down matmuls of its ``s × top_k × held
    / router width`` expected held picks, and the bytes of those picks'
    own rows (the input row read, the output row written, in bfloat16).
    The experts' weights are left out of the bytes: one read of them
    serves every prompt of an admission batch, so a prompt's share of it
    depends on what it was batched with."""
    d, f = model["d_model"], model["moe_d_ff"]
    rows = int(prompt_len) * _held_share(model)
    nl = model["num_layers"]
    return nl * rows * 3 * 2 * d * f, nl * rows * 2 * d * 2


def expert_round_needed(model: dict, live: float) -> Tuple[float, float]:
    """(FLOPs, bytes) of the held experts in one decode round of ``live``
    tokens, in every layer: the gate, up and down matmuls of the tokens'
    expected held picks and those picks' rows, and one read of the
    weights of each held expert that some token picks: ``held × (1 −
    (1 − top_k / router width)^live)`` experts under uniform routing."""
    d, f, nl = model["d_model"], model["moe_d_ff"], model["num_layers"]
    rows = live * _held_share(model)
    miss = 1.0 - model["top_k"] / model["router_experts"]
    used = model["num_experts"] * (1.0 - miss ** live)
    return (nl * rows * 3 * 2 * d * f,
            nl * (used * 3 * d * f + rows * 2 * d) * 2)
