"""Shared neural building blocks (pure-JAX functional, param pytrees).

Conventions
-----------
* Params are nested dicts of arrays; per-layer params are STACKED on a
  leading L axis and consumed with ``jax.lax.scan`` (fast compile for
  61-layer models, uniform HLO for the dry-run).
* Activations carry layout [B, S, H]; attention internals [B, S, n, d].
* Weights init in fp32 then cast to ``dtype``; math in bf16 with fp32
  softmax/normalization (MXU-faithful numerics).
* Everything here is initializable under ``jax.eval_shape`` — the dry-run
  never allocates real parameters.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
Params = Dict[str, Any]

# Query-chunk length for memory-bounded (flash-style) attention.
ATTN_CHUNK = 512

# Cost-calibration mode (set by launch.dryrun probes): fully unroll every
# scan so XLA cost_analysis sees each iteration.  XLA counts a while-loop
# BODY once regardless of trip count, so scanned-layer FLOPs/bytes/
# collective counts are ~L× under-reported; the dry-run lowers small-L
# unrolled probes and extrapolates linearly (see launch.dryrun.calibrate).
COST_EXACT = False

# Inference-path score dtype override (set by launch.dryrun --score-bf16):
# storing the [qc, T] scores bf16 halves the dominant prefill byte stream;
# softmax max-subtraction keeps bf16 exp stable (inference-quality knob,
# §Perf B3).
SCORE_DTYPE = None


def ckpt(fn, cfg, static_argnums=()):
    """jax.checkpoint honoring cfg.remat / cfg.remat_policy ("dots" saves
    matmul outputs so the backward recomputes only cheap elementwise ops —
    trades a little memory for a big cut in recompute bytes)."""
    if not cfg.remat:
        return fn
    policy = None
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(fn, static_argnums=static_argnums, policy=policy)


def xscan(f, init, xs, length=None):
    """lax.scan that fully unrolls under COST_EXACT (trace-time switch)."""
    return jax.lax.scan(f, init, xs, length=length,
                        unroll=True if COST_EXACT else 1)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype,
               use_bias: bool = False) -> Params:
    w = jax.random.normal(key, (in_dim, out_dim), jnp.float32)
    w = w * (in_dim ** -0.5)
    p = {"w": w.astype(dtype)}
    if use_bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def embed_init(key, vocab: int, dim: int, dtype) -> Params:
    # std 1/√dim keeps tied-head logits O(1) (the √dim input multiplier in
    # tied models restores unit-scale embeddings).
    return {"w": (jax.random.normal(key, (vocab, dim), jnp.float32)
                  * dim ** -0.5).astype(dtype)}


def norm_init(dim: int, dtype, with_bias: bool = False) -> Params:
    p = {"scale": jnp.ones((dim,), dtype)}
    if with_bias:
        p["bias"] = jnp.zeros((dim,), dtype)
    return p


# ---------------------------------------------------------------------------
# Primitive ops
# ---------------------------------------------------------------------------

def dense(p: Params, x: Array) -> Array:
    y = jnp.einsum("...h,hn->...n", x, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y


def rmsnorm(p: Params, x: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm(p: Params, x: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if "bias" in p:
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def activation_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": functools.partial(jax.nn.gelu,
                                                           approximate=True),
            "geglu": functools.partial(jax.nn.gelu, approximate=True),
            "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max_pos: int, beta_fast: float,
                     beta_slow: float) -> Array:
    """YaRN inverse frequencies [d/2], as the Hugging Face ``rope_type:
    "yarn"``: dimension pairs that turn more than ``beta_fast`` times over
    ``original_max_pos`` positions keep θ^(−2i/d), those that turn fewer
    than ``beta_slow`` times are divided by ``factor``, and a linear ramp
    between the two (corrections floored and ceiled) blends them."""
    import math
    d = head_dim

    def corr(rot):
        return d * math.log(original_max_pos / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(beta_fast)), 0)
    hi = min(math.ceil(corr(beta_slow)), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    extra = rope_frequencies(d, theta)
    keep = 1.0 - ramp                       # share of the unscaled frequency
    return extra / factor * (1.0 - keep) + extra * keep


def rope_of(cfg, kind: str) -> Tuple[Array, float]:
    """(inverse frequencies [d/2], cos/sin scale) of one layer kind:
    YaRN for ``"full"`` layers when the config sets it, else plain RoPE at
    ``rope_theta`` with scale 1."""
    d = cfg.resolved_head_dim
    if kind == "full" and cfg.yarn_factor:
        return (yarn_frequencies(d, cfg.rope_theta, cfg.yarn_factor,
                                 cfg.yarn_original_max_pos,
                                 cfg.yarn_beta_fast, cfg.yarn_beta_slow),
                cfg.yarn_attention_factor)
    return rope_frequencies(d, cfg.rope_theta), 1.0


def apply_rope(x: Array, positions: Array, theta: float,
               freqs: Array = None, scale=1.0) -> Array:
    """x [..., S, n, d]; positions [..., S] (int).  Rotates pairs (even, odd).
    ``freqs`` (inverse frequencies [d/2]) replaces θ's plain ones, and cos
    and sin are multiplied by ``scale`` (YaRN's attention factor)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = rope_frequencies(d, theta)                   # [d/2]
    ang = positions.astype(jnp.float32)[..., None] * freqs   # [..., S, d/2]
    cos = jnp.cos(ang)[..., None, :]                          # [..., S, 1, d/2]
    sin = jnp.sin(ang)[..., None, :]
    if not (isinstance(scale, float) and scale == 1.0):
        cos, sin = cos * scale, sin * scale
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attention_init(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, dtype, use_bias: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, dtype, use_bias),
        "wk": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype,
                         use_bias),
        "wv": dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype,
                         use_bias),
        "wo": dense_init(ks[3], num_heads * head_dim, d_model, dtype, use_bias),
    }


def _split_heads(x: Array, n: int) -> Array:
    return x.reshape(x.shape[:-1] + (n, x.shape[-1] // n))


def _gqa_scores(q: Array, k: Array) -> Array:
    """q [B,S,nh,d], k [B,T,kvh,d] → scores [B,nh,S,T] (fp32 accum).

    Operands stay in their storage dtype (bf16) with fp32 MXU accumulation
    (preferred_element_type) — half the bytes of upcast-then-dot at the
    same numerics (§Perf iteration A4/B2)."""
    b, s, nh, d = q.shape
    kvh = k.shape[2]
    g = nh // kvh
    qg = q.reshape(b, s, kvh, g, d)
    sc = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                    preferred_element_type=jnp.float32)
    return sc.reshape(b, nh, s, k.shape[1])


def _gqa_pv(p: Array, v: Array) -> Array:
    """p [B,nh,S,T] (bf16 probs ok), v [B,T,kvh,d] → out [B,S,nh,d] fp32."""
    b, nh, s, t = p.shape
    kvh = v.shape[2]
    g = nh // kvh
    pg = p.reshape(b, kvh, g, s, t)
    out = jnp.einsum("bkgst,btkd->bskgd", pg, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, nh, v.shape[-1])


def attend(q: Array, k: Array, v: Array, positions: Array, *,
           causal: bool = True, chunk: int = 0,
           out_dtype=None, window=0) -> Array:
    """Softmax attention over precomputed q [B,S,nh,d], k/v [B,T,kvh,d],
    scanned over query chunks so the [qc, T] score block is the only S²
    activation (flash-style memory).  Returns [B, S, nh·d].  A causal
    ``window`` (static or traced int, 0 = none) hides key j from query i
    unless ``i − j < window``."""
    b, s, nh, hd = q.shape
    out_dtype = out_dtype or q.dtype
    scale = hd ** -0.5

    chunk = chunk or ATTN_CHUNK          # module global read at trace time
    qc = min(chunk, s)
    if s % qc != 0:                       # tiny smoke shapes
        qc = s
    n_chunks = s // qc

    def chunk_body(carry, qi):
        del carry
        q_blk = jax.lax.dynamic_slice_in_dim(q, qi * qc, qc, axis=1)
        sc = _gqa_scores(q_blk, k) * scale            # [B, nh, qc, T]
        if SCORE_DTYPE is not None:
            sc = sc.astype(SCORE_DTYPE)
        if causal:
            pos_blk = jax.lax.dynamic_slice_in_dim(positions, qi * qc, qc,
                                                   axis=-1)
            mask = pos_blk[..., None] >= positions[..., None, :]  # [B, qc, T]
            if not (isinstance(window, int) and window == 0):
                mask = mask & (pos_blk[..., None] - positions[..., None, :]
                               < window)
            sc = jnp.where(mask[:, None, :, :], sc,
                           jnp.asarray(-1e30, sc.dtype))
        pr = jax.nn.softmax(sc, axis=-1).astype(v.dtype)   # bf16 probs
        return None, _gqa_pv(pr, v).astype(out_dtype)  # [B, qc, nh, d]

    if n_chunks == 1:
        _, out = chunk_body(None, 0)
    else:
        # Remat each chunk: backward recomputes the [qc, T] probs instead of
        # saving n_chunks of them (flash-attention-style S² memory avoidance).
        _, outs = xscan(jax.checkpoint(chunk_body), None,
                        jnp.arange(n_chunks))
        out = jnp.moveaxis(outs, 0, 1).reshape(b, s, nh, hd)
    return out.reshape(b, s, nh * hd)


def causal_attention(p: Params, x: Array, cfg, positions: Array,
                     chunk: int = 0, causal: bool = True,
                     kind=None) -> Array:
    """Standard self-attention block body (projections + attend + out-proj).
    ``kind`` — ``(inverse frequencies, scale, window)``, each static or
    traced per layer (``layer_kinds_table``) — sets the layer's RoPE and
    causal window; None is plain RoPE over the whole context."""
    nh, kvh = cfg.num_heads, cfg.num_kv_heads
    freqs, scale, window = kind if kind is not None else (None, 1.0, 0)
    q = _split_heads(dense(p["wq"], x), nh)
    k = _split_heads(dense(p["wk"], x), kvh)
    v = _split_heads(dense(p["wv"], x), kvh)
    q = apply_rope(q, positions, cfg.rope_theta, freqs, scale)
    k = apply_rope(k, positions, cfg.rope_theta, freqs, scale)
    out = attend(q, k, v, positions, causal=causal, chunk=chunk,
                 out_dtype=x.dtype, window=window)
    return dense(p["wo"], out)


#: a window that hides nothing: stands for a full-attention layer in a
#: per-layer table of windows
NO_WINDOW = 1 << 30


def layer_kinds_table(cfg, layers=None):
    """Per-layer ``(inverse frequencies [L, d/2], scale [L], window [L])``
    of ``cfg.layer_kinds`` (or of those of ``layers``), for a layer scan
    whose layers differ in kind."""
    kinds = [cfg.layer_kinds[i] for i in (layers if layers is not None
                                          else range(cfg.num_layers))]
    tabs = {k: rope_of(cfg, k) for k in set(kinds)}
    freqs = jnp.stack([tabs[k][0] for k in kinds])
    scale = jnp.asarray([tabs[k][1] for k in kinds], jnp.float32)
    window = jnp.asarray([cfg.sliding_window if k == "window" else NO_WINDOW
                          for k in kinds], jnp.int32)
    return freqs, scale, window


def cross_attention(p: Params, x: Array, memory_kv: Tuple[Array, Array],
                    cfg) -> Array:
    """Cross-attention against precomputed memory K/V [B, M, kvh, d].

    Uses the same query-chunked ``attend`` as self-attention — a dense
    [B, nh, S, M] score tensor at S = M = 4k would be tens of GB fp32.
    """
    nh = cfg.num_heads
    b, s, _ = x.shape
    q = _split_heads(dense(p["wq"], x), nh)
    k, v = memory_kv
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    out = attend(q, k, v, positions, causal=False, out_dtype=x.dtype)
    return dense(p["wo"], out)


def memory_kv(p: Params, memory: Array, kvh: int) -> Tuple[Array, Array]:
    return (_split_heads(dense(p["wk"], memory), kvh),
            _split_heads(dense(p["wv"], memory), kvh))


# -- KV-cache decode --------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, kvh: int, hd: int, dtype):
    shape = (batch, max_len, kvh, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_attention(p: Params, x: Array, cache: Params, pos: Array, cfg,
                     kind=None) -> Tuple[Array, Params]:
    """One-token attention: x [B, 1, H], cache k/v [B, T, kvh, d], pos [B].
    ``kind`` as in :func:`causal_attention`."""
    nh, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    freqs, scale, window = kind if kind is not None else (None, 1.0, 0)
    b = x.shape[0]
    t = cache["k"].shape[1]
    q = _split_heads(dense(p["wq"], x), nh)            # [B, 1, nh, d]
    k_new = _split_heads(dense(p["wk"], x), kvh)
    v_new = _split_heads(dense(p["wv"], x), kvh)
    q = apply_rope(q, pos[:, None], cfg.rope_theta, freqs, scale)
    k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta, freqs, scale)

    def upd(c, new):
        return jax.vmap(
            lambda cb, nb, pb: jax.lax.dynamic_update_slice_in_dim(
                cb, nb, pb, axis=0))(c, new, pos)
    k = upd(cache["k"], k_new.astype(cache["k"].dtype))
    v = upd(cache["v"], v_new.astype(cache["v"].dtype))

    sc = _gqa_scores(q, k) * (hd ** -0.5)              # [B, nh, 1, T]
    valid = jnp.arange(t)[None, :] <= pos[:, None]     # [B, T]
    if kind is not None:
        valid = valid & (pos[:, None] - jnp.arange(t)[None, :] < window)
    sc = jnp.where(valid[:, None, None, :], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    out = _gqa_pv(pr, v).astype(x.dtype).reshape(b, 1, nh * hd)
    return dense(p["wo"], out), {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, dtype, gated: bool,
             use_bias: bool = False) -> Params:
    ks = jax.random.split(key, 3)
    p = {"up": dense_init(ks[0], d_model, d_ff, dtype, use_bias),
         "down": dense_init(ks[1], d_ff, d_model, dtype, use_bias)}
    if gated:
        p["gate"] = dense_init(ks[2], d_model, d_ff, dtype, use_bias)
    return p


def mlp(p: Params, x: Array, activation: str) -> Array:
    act = activation_fn(activation)
    if "gate" in p:
        h = act(dense(p["gate"], x)) * dense(p["up"], x)
    else:
        h = act(dense(p["up"], x))
    return dense(p["down"], h)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def cross_entropy(logits: Array, labels: Array,
                  ignore_id: int = -1) -> Array:
    """Mean next-token CE; fp32 log-softmax; labels==ignore_id masked.

    The label logit is picked with a masked reduction (NOT take_along_axis):
    a gather on the vocab axis would force GSPMD to all-gather the
    vocab-sharded [B, S, V] logits; the where+sum fuses into a sharded
    reduction with a [B, S] all-reduce instead.
    """
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
    ll = jnp.sum(jnp.where(vocab_iota == labels[..., None], lg, 0.0), axis=-1)
    nll = lse - ll
    mask = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
