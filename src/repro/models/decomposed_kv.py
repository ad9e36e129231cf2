"""Decomposed KV cache — the paper's activation decomposition applied to
serving memory (beyond-paper §Perf feature).

Decode is KV-bandwidth-bound: every step re-reads the whole [T, kvh·hd]
cache.  K and V are activations, so D-com's machinery applies directly:
after prefill, each layer's K/V is Lanczos-decomposed into
(U [B, T, r], Vᵀ [B, r, kvh·hd]); per decode step the attention contracts
THROUGH the factors —

  scores = (q · Vᵀ_kᵀ) · Uᵀ_k        (r·d + T·r  vs  T·d  per head-group)
  out    = ((p · U_v) · Vᵀ_v)

so cache bytes read per step shrink by ~d_kv/r (Eq. 10 applied to the KV
stream).  New tokens append to a small DENSE TAIL (exact attention over
recent context); the serving engine re-compresses the tail into the
low-rank prefix on a fixed cadence (rank-concat + retruncate, amortized) —
mirroring the paper's "decomposition once, consumed many times" economics.

All tail state is PER SLOT: ``frozen_len`` may be a ``[B]`` vector (each
slot's low-rank prefix length), the prefix rows beyond a slot's
``frozen_len`` are masked out of the softmax, ``compress_tail`` accepts a
per-slot ``fold`` mask so each slot folds exactly when ITS tail fills, and
``splice_dkv`` scatters a freshly prefilled low-rank prefix + empty tail
into a live cache along the batch axis — the serving engine admits new
requests without touching live slots.

Approximation surface: the low-rank prefix (rank r of the RoPE'd K/V rows).
``prefill_dkv`` at full rank reproduces dense attention exactly
(tests/test_decomposed_kv.py).

A PAGED twin of the slab layout lives at the bottom of this module
(``init_paged_cache`` / ``gather_pages`` / ``decode_step_dkv_paged`` /
``compress_tail_paged`` / ``prefill_suffix_dkv``): prefix U rows and dense
tail rows sit in fixed-size page pools addressed by per-slot block tables,
enabling refcounted SHARING of frozen prefix pages across requests
(serving.paged) while replaying the slab arithmetic bit-for-bit.

Layer kinds.  Each layer attends by its kind (``cfg.layer_kinds``): a
``"full"`` layer through its factors and dense tail as above, a
``"window"`` layer to a RING of its last ``sliding_window`` RoPE'd K/V
rows, ``ring.k``/``ring.v`` [nw, B, W, kvh, hd] — the row of position p
sits at ``p mod W``.  The cache holds factors and a tail for the full
layers only (``factorized_layers``) and a ring for each window layer
(``window_layers``).  Prefill factorizes only the full layers' K/V and
writes each window layer's last min(len, W) rows; decode writes ring row
``pos mod W`` and masks rows outside the window; a splice carries both
kinds and a fold touches only the factors.  The MLP follows the
configuration: a dense MLP, or, with ``router_experts`` set, the
drop-free held-expert layer.  One prefill body and one decode-step body
scan one PERIOD of the layer pattern at a time (``cfg.full_attn_every``
layers where there are windows, else one), unrolled by kind: a dense
model is the period ``("full",)`` with a dense MLP.  The expert weights
are not scanned: the grouped matmul (``ragged_dot``) is a custom call
that takes no fused slice, so a per-period or per-layer slice of them
would be copied out before every call.  The period body gets the whole
[L, held, …] stacks as loop constants and hands
``moe.held_experts_ffn`` the layer's index; it views a stack as
[L·held, …] groups (a bitcast) and puts the layer's runs at the layer's
groups.  Window attention and ring writes sit in the named scope
``dcom.window``, the expert layer in ``dcom.moe``.

Sharding invariants (mesh-parallel serving, DESIGN.md §9): every op in this
module is BATCH-LOCAL — the tail write is a vmapped
``dynamic_update_slice`` along each slot's own row, ``compress_tail``'s
scatter blocks are built per slot, and ``splice_dkv`` scatters along the
batch axis only — so a serving engine that DP-shards the slot axis (and
puts kvw on "model") never induces a cross-device gather on the decode hot
path.  ``k_u``/``v_u`` time axes stay model-replicated (the refuted §Perf
C3 experiment: sharded-softmax all-reduces over the [B,kvh,g,T] scores
cost 2× the saved U reads).
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..engine import DecomposeEngine, EngineConfig
from . import layers as L
from . import transformer as T

Array = jax.Array
Params = Dict[str, Any]

TAIL = 128                      # dense recent-token buffer length

# Module-default engine for callers that don't thread one (tests, one-shot
# scripts); serving constructs and reuses its own.
_DEFAULT_ENGINE = DecomposeEngine(EngineConfig())


def unsupported(cfg) -> Optional[str]:
    """Why the decomposed-KV path cannot serve ``cfg`` (None: it can).  It
    serves the dense family, and expert models whose experts run drop-free
    (``router_experts`` set, no leading dense layers): the capacity-factor
    ``moe_ffn`` drops tokens by what they are batched with, so admissions
    of different batch sizes would serve different tokens.  Either needs
    a full-attention layer to factorize."""
    if cfg.family != "dense" and not (
            cfg.family == "moe" and cfg.router_experts
            and not cfg.first_k_dense):
        return (f"decomposed KV serves the dense family and expert models "
                f"with a drop-free held-expert layer (router_experts); "
                f"{cfg.name} is family {cfg.family!r}")
    if "full" not in _period_kinds(cfg):
        return f"{cfg.name} has no full-attention layer to factorize"
    return None


def paged_unsupported(cfg) -> Optional[str]:
    """Why ``paged=True`` cannot serve ``cfg`` (None: it can): the page
    pools hold factors and a dense tail for a stack of full-attention
    layers with dense MLPs."""
    if not window_layers(cfg) and not cfg.router_experts:
        return None
    return (f"paged decomposed KV holds factors and a dense tail for a "
            f"stack of full-attention layers with dense MLPs; {cfg.name} "
            f"({len(window_layers(cfg))} window layers beside "
            f"{len(factorized_layers(cfg))} factorized ones, expert MLPs) "
            f"is served on the slab: paged=False")


def _period_kinds(cfg) -> Tuple[str, ...]:
    """The layer kinds of one period of the layer pattern."""
    n = cfg.full_attn_every if cfg.sliding_window and cfg.full_attn_every \
        else 1
    kinds = cfg.layer_kinds
    if cfg.num_layers % n or kinds != kinds[:n] * (cfg.num_layers // n):
        raise ValueError(f"{cfg.name}: {cfg.num_layers} layers are not "
                         f"whole periods of {n}")
    return kinds[:n]


def factorized_layers(cfg) -> Tuple[int, ...]:
    """Indices of the layers whose K/V is factorized (full attention)."""
    return tuple(i for i, k in enumerate(cfg.layer_kinds) if k == "full")


def window_layers(cfg) -> Tuple[int, ...]:
    """Indices of the layers that keep a ring of window rows."""
    return tuple(i for i, k in enumerate(cfg.layer_kinds) if k == "window")


def init_cache(cfg, batch: int, frozen_len: int, rank: int,
               tail: int = TAIL) -> Params:
    kvh, hd, dt = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.jax_dtype
    nf, nw = len(factorized_layers(cfg)), len(window_layers(cfg))
    z = jnp.zeros
    rows = lambda n, t: z((n, batch, t, kvh, hd), dt)
    cache = {
        "k_u": z((nf, batch, frozen_len, rank), dt),
        "k_vt": z((nf, batch, rank, kvh * hd), dt),
        "v_u": z((nf, batch, frozen_len, rank), dt),
        "v_vt": z((nf, batch, rank, kvh * hd), dt),
        "tail": {"k": rows(nf, tail), "v": rows(nf, tail)},
    }
    if nw:
        cache["ring"] = {"k": rows(nw, cfg.sliding_window),
                         "v": rows(nw, cfg.sliding_window)}
    return cache


# ---------------------------------------------------------------------------
# The layer body, by kind
# ---------------------------------------------------------------------------

def _embed(p: Params, cfg, tokens: Array) -> Array:
    return p["embed"]["w"][tokens] * jnp.asarray(
        cfg.d_model ** 0.5 if cfg.tie_embeddings else 1.0, cfg.jax_dtype)


def _by_period(tree, n: int):
    """Leaves [L, …] → [L / n, n, …]: one row per period of n layers.  A
    period of one layer is scanned as it lies: that reshape would cost
    the compiled program slices and copies of whole weight stacks."""
    if n == 1:
        return tree
    return jax.tree_util.tree_map(
        lambda a: a.reshape((a.shape[0] // n, n) + a.shape[1:]), tree)


def _at(row, j: int, n: int):
    """Layer j of a period row of n layers (laid out by _by_period)."""
    return row if n == 1 else jax.tree_util.tree_map(lambda a: a[j], row)


def _stack(rows):
    """Per-layer trees → one tree of [len(rows), …] leaves (None: none)."""
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *rows) \
        if rows else None


def _flat_periods(tree):
    """Leaves [P, n, …] → [P·n, …]."""
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), tree)


def _qkv(lp, h, cfg, positions, rope):
    """Projections of one layer, q and k rotated by the kind's RoPE."""
    freqs, scale = rope
    q = L._split_heads(L.dense(lp["attn"]["wq"], h), cfg.num_heads)
    k = L._split_heads(L.dense(lp["attn"]["wk"], h), cfg.num_kv_heads)
    v = L._split_heads(L.dense(lp["attn"]["wv"], h), cfg.num_kv_heads)
    q = L.apply_rope(q, positions, cfg.rope_theta, freqs, scale)
    k = L.apply_rope(k, positions, cfg.rope_theta, freqs, scale)
    return q, k, v


def _frozen_vec(frozen_len, pos: Array) -> Array:
    """Normalize frozen_len (int or per-slot [B] array) to int32 [B]."""
    return jnp.broadcast_to(jnp.asarray(frozen_len, jnp.int32), pos.shape)


def _lowrank_attention(q: Array, c: Params, recent: Params, q_pos: Array,
                       frozen_len: Array, cfg) -> Array:
    """q [B, S, nh, d] at positions q_pos [B, S]; low-rank prefix + dense
    recent rows → out [B, S, nh·d].

    ``recent`` k/v [B, R, kvh, d] hold the rows after the prefix: row j
    is position ``frozen_len + j``, visible to a query where that is at
    most its position (decode: the tail, S = 1; a prefix-hit prefill: the
    suffix itself, causally).  ``frozen_len`` is per-slot [B]: prefix rows
    at or beyond a slot's frozen_len are zero in U but still produce score
    0 (not −inf) through the factors, so they are masked out of the
    softmax explicitly.
    """
    nh, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    g = nh // kvh
    b, s = q.shape[:2]
    scale = hd ** -0.5
    qg = q.reshape(b, s, kvh, g, hd).astype(jnp.float32)
    t_pre = c["k_u"].shape[1]                     # static prefix row count

    # ---- prefix scores through the factors ------------------------------
    k_vt = c["k_vt"].astype(jnp.float32).reshape(b, -1, kvh, hd)
    inner = jnp.einsum("bskgd,brkd->bskgr", qg, k_vt)       # [B,S,kvh,g,r]
    sc_pre = jnp.einsum("bskgr,btr->bskgt", inner,
                        c["k_u"].astype(jnp.float32)) * scale
    pre_valid = jnp.arange(t_pre)[None, :] < frozen_len[:, None]   # [B,T]
    sc_pre = jnp.where(pre_valid[:, None, None, None, :], sc_pre, -1e30)

    # ---- recent-row scores (exact) ----------------------------------------
    rk = recent["k"].astype(jnp.float32)                      # [B,R,kvh,hd]
    sc_rec = jnp.einsum("bskgd,btkd->bskgt", qg, rk) * scale
    row_pos = frozen_len[:, None] + jnp.arange(rk.shape[1])[None, :]
    valid = row_pos[:, None, :] <= q_pos[:, :, None]          # [B, S, R]
    sc_rec = jnp.where(valid[:, :, None, None, :], sc_rec, -1e30)

    # ---- joint softmax -----------------------------------------------------
    pr = jax.nn.softmax(jnp.concatenate([sc_pre, sc_rec], axis=-1), axis=-1)
    p_pre, p_rec = pr[..., :t_pre], pr[..., t_pre:]

    # ---- PV through the factors -------------------------------------------
    tmp = jnp.einsum("bskgt,btr->bskgr", p_pre,
                     c["v_u"].astype(jnp.float32))
    v_vt = c["v_vt"].astype(jnp.float32).reshape(b, -1, kvh, hd)
    out = jnp.einsum("bskgr,brkd->bskgd", tmp, v_vt)
    out = out + jnp.einsum("bskgt,btkd->bskgd", p_rec,
                           recent["v"].astype(jnp.float32))
    return out.reshape(b, s, nh * hd)


def _ring_of(a: Array, w: int) -> Array:
    """The ring of a prompt's rows a [B, S, …]: its last min(S, w) rows,
    the row of position p at ``p mod w`` (zeros where S < w)."""
    s = a.shape[1]
    if s < w:
        return jnp.pad(a, [(0, 0), (0, w - s)] + [(0, 0)] * (a.ndim - 2))
    return jnp.roll(a[:, s - w:], s % w, axis=1)


def _ring_attention(q: Array, rk: Array, rv: Array, pos: Array,
                    cfg) -> Array:
    """One-token attention over a window ring: q [B, 1, nh, d], ring
    k/v [B, W, kvh, d] → [B, 1, nh·d].  Ring row j holds position
    ``pos − ((pos − j) mod W)``, valid where that is not negative."""
    b, w = rk.shape[:2]
    hd = cfg.resolved_head_dim
    j = jnp.arange(w)[None, :]
    row_pos = pos[:, None] - (pos[:, None] - j) % w              # [B, W]
    sc = L._gqa_scores(q, rk) * (hd ** -0.5)                     # [B,nh,1,W]
    sc = jnp.where((row_pos >= 0)[:, None, None, :], sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1).astype(rv.dtype)
    return L._gqa_pv(pr, rv).reshape(b, 1, cfg.num_heads * hd)


def _expert_stacks(cfg, layers: Params) -> Tuple[Params, Optional[Params]]:
    """Layer weights [L, …] → (the same without the expert weights, the
    expert weights' whole stacks [L, held, …]; None without held
    experts).  The stacks stay out of the period scan's ``xs``: a scan
    would slice them per period and per layer, and the grouped matmul, a
    custom call, takes no fused slice, so each slice would be a copy."""
    if not cfg.router_experts:
        return layers, None
    rest = dict(layers["moe"])
    stacks = {w: rest.pop(w) for w in ("w_gate", "w_up", "w_down")}
    return dict(layers, moe=rest), stacks


def _mlp(lp, x, cfg, stacks: Optional[Params], layer: Array):
    """x plus the layer's MLP, and the token→expert picks: the dense MLP
    (in ``dcom.forward``; no picks) without expert ``stacks``, else the
    held experts on the whole stacks at the layer's group offset."""
    if stacks is None:
        with jax.named_scope("dcom.forward"):
            return x + L.mlp(lp["mlp"], T._norm(lp["mlp_norm"], x, cfg),
                             cfg.activation), None
    from . import moe
    h = T._norm(lp["mlp_norm"], x, cfg)
    y, _, picks = moe.held_experts_ffn(dict(lp["moe"], **stacks), h, cfg,
                                       layer)
    return x + y, picks


# ---------------------------------------------------------------------------
# Prefill and decode
# ---------------------------------------------------------------------------

def prefill_dkv(p: Params, cfg, tokens: Array, rank: int,
                tail: int = TAIL, exact: bool = False,
                engine: Optional[DecomposeEngine] = None
                ) -> Tuple[Array, Params]:
    """Prefill that emits a decomposed KV cache: (logits [B, V], cache),
    and for a model with held experts a third array, picks [router width]
    int32: the token→expert picks of the rows whose token is not the
    engine's pad id 0, summed over layers.

    The forward pass sits in the named scope ``dcom.forward`` (norms,
    projections, full attention, dense MLP, head), beside ``dcom.window``
    (window attention and the ring write) and ``dcom.moe``; then the full
    layers' K and V, and only theirs, go through
    :meth:`DecomposeEngine.decompose_kv` in ``dcom.lanczos`` (Lanczos via
    the engine's backend; ``exact`` switches to direct SVD for r near full
    rank, where floating-point Lanczos loses trailing directions — §2.3:
    Lanczos is the small-rank algorithm).  Compiled HLO carries the scopes
    in each instruction's ``op_name``, so a device trace splits admission
    by them.
    """
    if rank < 1:
        raise ValueError(f"prefill_dkv needs rank >= 1, got {rank} "
                         "(is the engine's kv_rank configured?)")
    engine = engine or _DEFAULT_ENGINE
    b, s = tokens.shape
    kinds = _period_kinds(cfg)
    n = len(kinds)
    ropes = {k: L.rope_of(cfg, k) for k in set(kinds)}
    w = cfg.sliding_window
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    positions = jnp.broadcast_to(jnp.arange(s), tokens.shape)
    with jax.named_scope("dcom.forward"):
        x = _embed(p, cfg, tokens)
    layers, stacks = _expert_stacks(cfg, p["layers"])
    real = (tokens != 0).reshape(-1).astype(jnp.int32)          # [B·S]

    def period(carry, inp):
        (x, picks), (lp, i) = carry, inp
        full, ring = [], []
        for j, kind in enumerate(kinds):
            lj = _at(lp, j, n)
            with jax.named_scope("dcom.forward"):
                h = T._norm(lj["attn_norm"], x, cfg)
                q, k, v = _qkv(lj, h, cfg, positions, ropes[kind])
            if kind == "window":
                with jax.named_scope("dcom.window"):
                    a = L.attend(q, k, v, positions, window=w,
                                 out_dtype=x.dtype)
                    ring.append({"k": _ring_of(k, w), "v": _ring_of(v, w)})
            else:
                with jax.named_scope("dcom.forward"):
                    a = L.attend(q, k, v, positions, out_dtype=x.dtype)
                full.append({"k": k.astype(cfg.jax_dtype),
                             "v": v.astype(cfg.jax_dtype)})
            with jax.named_scope("dcom.forward"):
                x = x + L.dense(lj["attn"]["wo"], a)
            x, e = _mlp(lj, x, cfg, stacks, i * n + j)
            if e is not None:
                with jax.named_scope("dcom.moe"):
                    picks = picks.at[e].add(real[:, None])
        return (x, picks), (_stack(full), _stack(ring))

    picks0 = None if stacks is None \
        else jnp.zeros((cfg.router_width,), jnp.int32)
    # The scan's own ops (weight slices, K/V row writes) are forward
    # work.  A trace reader takes an op's outermost dcom scope, so the
    # scan is scoped only where no window or expert scope nests inside.
    nested = stacks is not None or "window" in kinds
    with contextlib.nullcontext() if nested \
            else jax.named_scope("dcom.forward"):
        (x, picks), (full, ring) = L.xscan(
            period, (x, picks0), (_by_period(layers, n),
                                  jnp.arange(cfg.num_layers // n)))
    with jax.named_scope("dcom.forward"):
        logits = T.logits_head(p, x[:, -1:, :], cfg)[:, 0]
    full = _flat_periods(full)                       # [nf, B, S, kvh, hd]
    nf = full["k"].shape[0]

    def one(kv):
        with jax.named_scope("dcom.lanczos"):
            flat = kv.reshape(nf * b, s, kvh * hd)
            u, vt = engine.decompose_kv(flat, rank, exact=exact)
            r_eff = u.shape[-1]      # rank caps at min(s, kvw) (exact SVD)
            return (u.reshape(nf, b, s, r_eff),
                    vt.reshape(nf, b, r_eff, kvh * hd))

    k_u, k_vt = one(full["k"])
    v_u, v_vt = one(full["v"])
    z = jnp.zeros((nf, b, tail, kvh, hd), cfg.jax_dtype)
    cache = {"k_u": k_u, "k_vt": k_vt, "v_u": v_u, "v_vt": v_vt,
             "tail": {"k": z, "v": z}}
    if ring is not None:
        cache["ring"] = _flat_periods(ring)
    return (logits, cache) if stacks is None else (logits, cache, picks)


def decode_step_dkv(p: Params, cfg, token: Array, cache: Params,
                    pos: Array, frozen_len) -> Tuple[Array, Params]:
    """One-token decode over the decomposed cache: window layers attend
    to their ring (row ``pos mod W`` written first), full layers through
    their factors and dense tail.

    ``frozen_len`` is an int (uniform) or a per-slot int32 [B] vector; each
    slot's tail write position is its own ``pos − frozen_len``.
    """
    frozen_len = _frozen_vec(frozen_len, pos)
    kinds = _period_kinds(cfg)
    n, nfp = len(kinds), kinds.count("full")
    ropes = {k: L.rope_of(cfg, k) for k in set(kinds)}
    w = cfg.sliding_window
    x = _embed(p, cfg, token)[:, None, :]
    upd = lambda buf, new, at: jax.vmap(
        lambda bb, nn, ss: jax.lax.dynamic_update_slice_in_dim(
            bb, nn, ss, axis=0))(buf, new.astype(buf.dtype), at)
    fac = {k: _by_period(cache[k], nfp)
           for k in ("k_u", "k_vt", "v_u", "v_vt", "tail")}
    rings = _by_period(cache["ring"], n - nfp) if n > nfp else None
    layers, stacks = _expert_stacks(cfg, p["layers"])

    def period(x, inp):
        lp, i, f, rg = inp
        tails, new_rings = [], []
        fi = wi = 0
        for j, kind in enumerate(kinds):
            lj = _at(lp, j, n)
            h = T._norm(lj["attn_norm"], x, cfg)
            q, k, v = _qkv(lj, h, cfg, pos[:, None], ropes[kind])
            if kind == "window":
                with jax.named_scope("dcom.window"):
                    r = _at(rg, wi, n - nfp)
                    r = {"k": upd(r["k"], k, pos % w),
                         "v": upd(r["v"], v, pos % w)}
                    a = _ring_attention(q, r["k"], r["v"], pos, cfg)
                new_rings.append(r)
                wi += 1
            else:
                slot = pos - frozen_len          # tail write position
                t = _at(f["tail"], fi, nfp)
                t = {"k": upd(t["k"], k, slot), "v": upd(t["v"], v, slot)}
                layer_c = {key: _at(f[key], fi, nfp)
                           for key in ("k_u", "k_vt", "v_u", "v_vt")}
                a = _lowrank_attention(q, layer_c, t, pos[:, None],
                                       frozen_len, cfg)
                tails.append(t)
                fi += 1
            x = x + L.dense(lj["attn"]["wo"], a.astype(x.dtype))
            x, _ = _mlp(lj, x, cfg, stacks, i * n + j)
        return x, (_stack(tails), _stack(new_rings))

    x, (tails, new_rings) = L.xscan(
        period, x, (_by_period(layers, n), jnp.arange(cfg.num_layers // n),
                    fac, rings))
    new_cache = dict(cache)
    new_cache["tail"] = _flat_periods(tails)
    if new_rings is not None:
        new_cache["ring"] = _flat_periods(new_rings)
    return T.logits_head(p, x, cfg)[:, 0], new_cache


def decode_block_dkv(p: Params, cfg, token: Array, cache: Params, pos: Array,
                     frozen_len, n_steps, stop_table: Array, key, round0, *,
                     sampler, max_block: int):
    """Fused multi-step decode over the decomposed slab cache: up to
    ``n_steps`` (≤ the static ``max_block``) single-token steps inside one
    bounded on-device loop (:func:`api.run_decode_block`), sampling on
    device and exiting early on any stop-token emission.

    ``frozen_len`` is loop-invariant by construction — the serving engine
    caps ``n_steps`` at ``dkv_tail − max(occupancy)`` so every tail fold
    still happens at a block boundary, on the host, at exactly the
    occupancy the single-step engine would have folded at.

    Returns ``(token_buf [max_block, B], steps_done, done_mask, cache)``.
    """
    from . import api
    frozen = _frozen_vec(frozen_len, pos)
    step = lambda t, c, ps: decode_step_dkv(p, cfg, t, c, ps, frozen)
    return api.run_decode_block(step, sampler, max_block, token, cache,
                                pos, n_steps, stop_table, key, round0)


def fold_rank(rank: int, r_in: int, t_frozen: int, tl: int) -> int:
    """The rank a fold retruncates to — host-side mirror of the cap
    inside :func:`compress_tail` (configured rank, bounded by the
    concatenated factor width and the row count).  The serving engine uses
    it to track per-slot effective rank without touching device data."""
    return min(rank, r_in + tl, t_frozen + tl)


def compress_tail(cache: Params, cfg, rank: int,
                  frozen_len=None, fold=None, new_frozen=None) -> Params:
    """Fold the dense tail into the low-rank prefix (rank-concat +
    retruncate).

    Uniform mode (``frozen_len is None``): every slot's tail occupies rows
    ``t_frozen … t_frozen+tl`` — the pre-per-slot behavior, kept for
    one-shot callers (tests, ``api.decomposed_fns``).

    Per-slot mode: ``frozen_len`` is an int32 [B] vector and ``fold`` a
    bool [B] mask — each folding slot's tail rows are scattered at ITS
    ``frozen_len`` offset in the row space, non-folding slots keep their
    prefix, factors, and tail untouched (time axis still grows by ``tl``
    so shapes stay static; the serving engine slices back to
    ``max(frozen_len)``).

    ``new_frozen`` (per-slot mode, int32 [B]: each folding slot's
    post-fold prefix length, i.e. its ``pos``) zeroes the retruncated U
    rows at or beyond the new frozen length.  Those rows reconstruct to
    ~0 anyway (they fold zero tail rows), but the explicit zero enforces
    the module invariant "prefix rows beyond frozen_len are zero" BITWISE
    — which is what lets the paged engine store exactly
    ``ceil(frozen_len/page)`` pages per slot and still replay the slot
    engine's arithmetic identically.
    """
    from ..core.lowrank import LowRank, retruncate
    nl, b, tl, kvh, hd = cache["tail"]["k"].shape
    kvw = kvh * hd
    r_in = cache["k_u"].shape[-1]
    t_frozen = cache["k_u"].shape[2]
    # A fold RETRUNCATES BACK to the configured rank: r_fold caps at
    # ``rank`` (and at the concatenated factor width / row count, which
    # bound the content rank).  Uniform mode folds every slot, so the
    # output width is exactly r_fold — a cache whose factors were inflated
    # past ``rank`` by heterogeneous splices shrinks back on the next fold
    # instead of ratcheting (the old ``r_out = max(r_in, r_fold)``
    # permanently kept the widest rank any splice ever introduced).
    # Per-slot mode must keep the non-folding slots' r_in columns
    # bit-identical, so the ARRAY stays max-width there; folded slots'
    # columns beyond r_fold are zero and the serving engine slices the
    # rank axis down to the widest live slot (``rank_eff`` bookkeeping).
    r_fold = min(rank, r_in + tl, t_frozen + tl)
    r_out = r_fold if frozen_len is None else max(r_in, r_fold)

    if frozen_len is None:
        offsets = jnp.full((b,), t_frozen, jnp.int32)
        fold_m = jnp.ones((b,), bool)
    else:
        offsets = jnp.asarray(frozen_len, jnp.int32).reshape(b)
        fold_m = jnp.ones((b,), bool) if fold is None \
            else jnp.asarray(fold).reshape(b)

    # identity scatter block per slot: E[offset+i, i] = 1  → [B, T+tl, tl]
    eye = jnp.eye(tl, dtype=jnp.float32)
    scat = jax.vmap(lambda off: jax.lax.dynamic_update_slice(
        jnp.zeros((t_frozen + tl, tl), jnp.float32), eye, (off, 0)))(offsets)

    def one(u, vt, tail):
        tail2 = tail.reshape(nl, b, tl, kvw).astype(jnp.float32)
        u2 = u.astype(jnp.float32)                       # [nl, b, T, r]
        vt2 = vt.astype(jnp.float32)                     # [nl, b, r, kvw]
        u_pad = jnp.pad(u2, ((0, 0), (0, 0), (0, tl), (0, 0)))
        u_cat = jnp.concatenate(
            [u_pad, jnp.broadcast_to(scat[None], (nl,) + scat.shape)],
            axis=-1)                                     # [nl,b,T+tl,r+tl]
        vt_cat = jnp.concatenate([vt2, tail2], axis=-2)
        lr = retruncate(LowRank(u_cat,
                                jnp.ones(u_cat.shape[:-2]
                                         + (u_cat.shape[-1],), u_cat.dtype),
                                vt_cat), r_fold)
        pad_r = lambda a, ax: jnp.pad(
            a, [(0, 0)] * ax + [(0, r_out - a.shape[ax])]
            + [(0, 0)] * (a.ndim - ax - 1))
        u_new, vt_new = pad_r(lr.scaled_u(), 3), pad_r(lr.vt, 2)
        if new_frozen is not None:
            nf = jnp.asarray(new_frozen, jnp.int32).reshape(b)
            row_ok = jnp.arange(t_frozen + tl)[None, :] < nf[:, None]
            u_new = jnp.where(row_ok[None, :, :, None], u_new, 0.0)
        if frozen_len is None:
            # uniform mode: every slot folds, so the retruncated factors
            # ARE the output (width exactly r_fold <= rank — no keep
            # branch, which could be wider than r_out)
            return u_new, vt_new
        # non-folding slots keep their (time-padded, rank-padded) factors
        keep_u, keep_vt = pad_r(u_pad, 3), pad_r(vt2, 2)
        fm = fold_m[None, :, None, None]
        return (jnp.where(fm, u_new, keep_u),
                jnp.where(fm, vt_new, keep_vt))

    k_u, k_vt = one(cache["k_u"], cache["k_vt"], cache["tail"]["k"])
    v_u, v_vt = one(cache["v_u"], cache["v_vt"], cache["tail"]["v"])
    fm = fold_m[None, :, None, None, None]
    new_tail = {k: jnp.where(fm, jnp.zeros_like(v), v)
                for k, v in cache["tail"].items()}
    out = {"k_u": k_u.astype(cache["k_u"].dtype),
           "k_vt": k_vt.astype(cache["k_vt"].dtype),
           "v_u": v_u.astype(cache["v_u"].dtype),
           "v_vt": v_vt.astype(cache["v_vt"].dtype),
           "tail": new_tail}
    if "ring" in cache:                 # window rows are never folded
        out["ring"] = cache["ring"]
    return out


def splice_dkv(live: Params, fresh: Params, slot_indices,
               src_indices=None) -> Params:
    """Scatter freshly prefilled rows of ``fresh`` (batch rows
    ``src_indices``, default 0…n−1) into ``live`` at ``slot_indices`` along
    the batch axis — admission into a LIVE decomposed cache, no re-prefill
    of occupied slots.

    Time and rank axes are zero-padded to the pairwise max first (zero U
    rows/columns and zero Vᵀ rows are inert), so a fresh short prefix can
    join a cache whose prefix has grown through tail folds, and vice
    versa.  Its ops sit in the named scope ``dcom.splice``.
    """
    with jax.named_scope("dcom.splice"):
        idx = jnp.asarray(slot_indices, jnp.int32)  # traced-input friendly
        src = jnp.arange(idx.shape[0], dtype=jnp.int32) \
            if src_indices is None \
            else jnp.asarray(src_indices, jnp.int32)

        def pad_to(a, axis, size):
            if a.shape[axis] >= size:
                return a
            w = [(0, 0)] * a.ndim
            w[axis] = (0, size - a.shape[axis])
            return jnp.pad(a, w)

        t = max(live["k_u"].shape[2], fresh["k_u"].shape[2])
        r = max(live["k_u"].shape[-1], fresh["k_u"].shape[-1])
        out: Params = {}
        for key in ("k_u", "v_u"):
            old = pad_to(pad_to(live[key], 2, t), 3, r)
            new = pad_to(pad_to(fresh[key], 2, t), 3, r)
            out[key] = old.at[:, idx].set(new[:, src].astype(old.dtype))
        for key in ("k_vt", "v_vt"):
            old = pad_to(live[key], 2, r)
            new = pad_to(fresh[key], 2, r)
            out[key] = old.at[:, idx].set(new[:, src].astype(old.dtype))
        for kind in ("tail", "ring") if "ring" in live else ("tail",):
            out[kind] = {k: live[kind][k].at[:, idx].set(
                fresh[kind][k][:, src].astype(live[kind][k].dtype))
                for k in live[kind]}
        return out


# ---------------------------------------------------------------------------
# Paged layout (vLLM-style block tables over the decomposed cache)
# ---------------------------------------------------------------------------
#
# Instead of one [slots, max_len, …] slab, the low-rank prefix U rows and
# the dense tail live in fixed-size PAGE POOLS indexed by per-slot page
# lists (block tables, host-side):
#
#   k_u_pages / v_u_pages  [nl, P,  page, r]          prefix U row pool
#   k_vt / v_vt            [nl, B,  r,    kvw]        per-slot factors
#   tail.k_pages / v_pages [nl, TP, page, kvh, hd]    dense tail row pool
#
# Page id 0 is a reserved WRITE SINK: block-table padding and the scatter
# targets of non-folding slots point at it, so one jitted scatter serves
# every fold without masking.  The sink's content is kept ALL-ZERO by
# construction (fold scatters mask non-folding rows to zero), because
# gather_pages' block-table padding reads it as if it were zero rows.  A
# page holds the same row range for EVERY layer (one block table per
# slot, not per layer), so the layer scan consumes gathered pages exactly
# like slab rows.
#
# Token-exactness contract: ``gather_pages`` + row/rank slicing to the
# slot engine's slab geometry reproduces the slab ARRAYS bit-for-bit
# (rows beyond a slot's frozen_len are zero — see ``new_frozen`` in
# :func:`compress_tail`), so paged decode/fold arithmetic is the slot
# engine's arithmetic, and shared prefix pages are safe to alias across
# slots because folds scatter into FRESH pages (copy-on-write).


def init_paged_cache(cfg, batch: int, num_pages: int, page: int, rank: int,
                     num_tail_pages: int) -> Params:
    """Page pools + per-slot factor slots for the paged decomposed cache."""
    kvw = cfg.num_kv_heads * cfg.resolved_head_dim
    nl, dt = cfg.num_layers, cfg.jax_dtype
    z = jnp.zeros
    return {
        "k_u_pages": z((nl, num_pages, page, rank), dt),
        "v_u_pages": z((nl, num_pages, page, rank), dt),
        "k_vt": z((nl, batch, rank, kvw), dt),
        "v_vt": z((nl, batch, rank, kvw), dt),
        "tail": {
            "k_pages": z((nl, num_tail_pages, page, cfg.num_kv_heads,
                          cfg.resolved_head_dim), dt),
            "v_pages": z((nl, num_tail_pages, page, cfg.num_kv_heads,
                          cfg.resolved_head_dim), dt),
        },
    }


def gather_pages(pool: Array, bt: Array, rows: Optional[int] = None
                 ) -> Array:
    """pool [nl, P, page, …], bt int32 [B, n] → rows [nl, B, t, …].

    Concatenates each slot's pages along the time axis; ``rows`` (static)
    pads with zeros or slices so the result matches a target slab length
    regardless of the block-table width.
    """
    g = pool[:, bt]                                  # [nl, B, n, page, ...]
    nl, b, n, pg = g.shape[:4]
    g = g.reshape(nl, b, n * pg, *g.shape[4:])
    if rows is not None:
        if rows <= n * pg:
            g = g[:, :, :rows]
        else:
            w = [(0, 0), (0, 0), (0, rows - n * pg)] \
                + [(0, 0)] * (g.ndim - 3)
            g = jnp.pad(g, w)
    return g


def scatter_pages(pool: Array, rows: Array, bt: Array) -> Array:
    """Write rows [nl, B, t, …] back into pool pages ``bt`` [B, n].

    ``t`` is zero-padded or sliced to ``n·page``; duplicate page ids (the
    id-0 write sink shared by padding and non-folding slots) are allowed —
    every sink write is zeros, so the sink stays all-zero regardless of
    scatter order.
    """
    nl, b, t = rows.shape[:3]
    n, page = bt.shape[1], pool.shape[2]
    want = n * page
    if t < want:
        w = [(0, 0), (0, 0), (0, want - t)] + [(0, 0)] * (rows.ndim - 3)
        rows = jnp.pad(rows, w)
    elif t > want:
        rows = rows[:, :, :want]
    rows = rows.reshape(nl, b, n, page, *rows.shape[3:])
    return pool.at[:, bt].set(rows.astype(pool.dtype))


def write_prefix_pages(pool: Array, u: Array, bt: Array, src: Array
                       ) -> Array:
    """Scatter freshly prefilled U factors (batch rows ``src`` of
    u [nl, nb, s, r_eff]) into pool pages ``bt`` [m, n]; the rank axis is
    zero-padded to the pool width (zero columns are inert)."""
    r = pool.shape[-1]
    u = u[:, src]
    if u.shape[-1] < r:
        u = jnp.pad(u, [(0, 0)] * (u.ndim - 1) + [(0, r - u.shape[-1])])
    return scatter_pages(pool, u, bt)


def _gathered_cache(cache: Params, bt_u: Array, bt_t: Array, t_need: int,
                    r_need: int, tail_len: int) -> Params:
    """Materialize the slot-engine slab view of a paged cache (sliced to
    the mirrored slab geometry, so downstream math is bit-identical)."""
    return {
        "k_u": gather_pages(cache["k_u_pages"], bt_u, t_need)[..., :r_need],
        "v_u": gather_pages(cache["v_u_pages"], bt_u, t_need)[..., :r_need],
        "k_vt": cache["k_vt"][:, :, :r_need],
        "v_vt": cache["v_vt"][:, :, :r_need],
        "tail": {
            "k": gather_pages(cache["tail"]["k_pages"], bt_t, tail_len),
            "v": gather_pages(cache["tail"]["v_pages"], bt_t, tail_len),
        },
    }


def decode_step_dkv_paged(p: Params, cfg, token: Array, cache: Params,
                          pos: Array, frozen_len, bt_u: Array, bt_t: Array,
                          t_need: int, r_need: int, tail_len: int
                          ) -> Tuple[Array, Params]:
    """One-token decode through the page tables: gather each slot's pages
    into the slab view, run the slot-engine step, scatter the updated tail
    rows back into the tail pool.  ``t_need``/``r_need``/``tail_len`` are
    the slot engine's (static) slab dims — the host mirrors them so the
    gathered arrays equal the slab bit-for-bit."""
    slab = _gathered_cache(cache, bt_u, bt_t, t_need, r_need, tail_len)
    logits, upd = decode_step_dkv(p, cfg, token, slab, pos, frozen_len)
    new = dict(cache)
    new["tail"] = {
        "k_pages": scatter_pages(cache["tail"]["k_pages"],
                                 upd["tail"]["k"], bt_t),
        "v_pages": scatter_pages(cache["tail"]["v_pages"],
                                 upd["tail"]["v"], bt_t),
    }
    return logits, new


def decode_block_dkv_paged(p: Params, cfg, token: Array, cache: Params,
                           pos: Array, frozen_len, bt_u: Array, bt_t: Array,
                           n_steps, stop_table: Array, key, round0,
                           t_need: int, r_need: int, tail_len: int, *,
                           sampler, max_block: int):
    """Fused multi-step paged decode: gather each slot's pages into the
    slab view ONCE, run the slab block loop, scatter the updated tail rows
    back at loop exit.

    The block tables and the low-rank prefix pool are loop-invariant —
    folds and admissions (the only writers of ``bt_u``/prefix pages) run
    at block boundaries on the host — so the per-step gather/scatter of
    :func:`decode_step_dkv_paged` collapses to one gather + one scatter
    per BLOCK while the in-loop arithmetic stays the slab engine's,
    bit-for-bit (the gathered slab equals the slot engine's arrays by the
    token-exactness contract above).
    """
    slab = _gathered_cache(cache, bt_u, bt_t, t_need, r_need, tail_len)
    buf, steps, done, upd = decode_block_dkv(
        p, cfg, token, slab, pos, frozen_len, n_steps, stop_table, key,
        round0, sampler=sampler, max_block=max_block)
    new = dict(cache)
    new["tail"] = {
        "k_pages": scatter_pages(cache["tail"]["k_pages"],
                                 upd["tail"]["k"], bt_t),
        "v_pages": scatter_pages(cache["tail"]["v_pages"],
                                 upd["tail"]["v"], bt_t),
    }
    return buf, steps, done, new


def compress_tail_paged(cache: Params, cfg, rank: int, frozen_len, fold,
                        new_frozen, bt_u: Array, bt_u_new: Array,
                        bt_t: Array, t_need: int, r_need: int,
                        tail_len: int) -> Params:
    """Paged tail fold: gather the slab view, run :func:`compress_tail`
    (identical arithmetic), then scatter the retruncated prefix rows into
    FRESH pages ``bt_u_new`` — old pages are never written, so prefix
    pages shared with other slots or the prefix cache stay intact
    (copy-on-write).  Non-folding slots' rows in ``bt_u_new`` point at the
    id-0 sink.  Returns the new pool cache; the caller updates block
    tables and releases the folded slots' old page refs."""
    slab = _gathered_cache(cache, bt_u, bt_t, t_need, r_need, tail_len)
    folded = compress_tail(slab, cfg, rank, frozen_len=frozen_len,
                           fold=fold, new_frozen=new_frozen)
    r_pool = cache["k_u_pages"].shape[-1]
    pad_r = lambda a, ax: a if a.shape[ax] >= r_pool else jnp.pad(
        a, [(0, 0)] * ax + [(0, r_pool - a.shape[ax])]
        + [(0, 0)] * (a.ndim - ax - 1))
    fm = jnp.asarray(fold).reshape(-1)[None, :, None, None]
    # Only FOLDING slots' rows are scattered (their rows at/beyond the new
    # frozen length are already zeroed via ``new_frozen``); non-folding
    # slots' rows — whose bt_u_new entries all point at the id-0 sink —
    # scatter as ZEROS.  This keeps the sink page all-zero FOREVER, which
    # gather_pages' block-table padding relies on: a sink read must
    # return exact zeros, not the residue of a previous fold.
    u_sc = lambda key: jnp.where(fm, pad_r(folded[key], 3), 0.0)
    vt_sel = lambda key: jnp.where(
        fm, pad_r(folded[key], 2).astype(cache[key].dtype),
        cache[key])
    return {
        "k_u_pages": scatter_pages(cache["k_u_pages"], u_sc("k_u"),
                                   bt_u_new),
        "v_u_pages": scatter_pages(cache["v_u_pages"], u_sc("v_u"),
                                   bt_u_new),
        "k_vt": vt_sel("k_vt"),
        "v_vt": vt_sel("v_vt"),
        "tail": {
            "k_pages": scatter_pages(cache["tail"]["k_pages"],
                                     folded["tail"]["k"], bt_t),
            "v_pages": scatter_pages(cache["tail"]["v_pages"],
                                     folded["tail"]["v"], bt_t),
        },
    }


def prefill_suffix_dkv(p: Params, cfg, tokens: Array, prefix: Params,
                       start: Array, slen: Array, tail_len: int
                       ) -> Tuple[Array, Params]:
    """Tail-only prefill for a prefix-cache hit (the paper's "decompose
    once, consume many times" economics applied across REQUESTS).

    ``tokens`` [B, S] is each slot's suffix beyond its matched frozen
    prefix, RIGHT-padded (rows at or beyond ``slen[b]`` are pad; causal
    masking keeps real rows from attending them).  ``prefix`` carries the
    gathered cached factors {k_u/v_u [nl, B, L, r], k_vt/v_vt
    [nl, B, r, kvw]}; ``start`` [B] (= the matched prefix length, the
    slot's frozen_len) sets absolute RoPE positions ``start + i``.

    Returns (logits at each slot's LAST real row [B, V], dense tails
    [nl, B, tail_len, kvh, hd] with rows >= slen zeroed) — exactly the
    per-slot state a full prefill of prefix+suffix would have produced,
    without re-running the prefix forward OR its Lanczos factorization.
    """
    b, s = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    slen = jnp.asarray(slen, jnp.int32)
    x = _embed(p, cfg, tokens)
    positions = start[:, None] + jnp.arange(s)[None, :]
    live_row = jnp.arange(s)[None, :] < slen[:, None]    # [B, S] real rows
    rope = L.rope_of(cfg, "full")

    def scan_fn(x, inp):
        lp, c = inp
        h = T._norm(lp["attn_norm"], x, cfg)
        q, k, v = _qkv(lp, h, cfg, positions, rope)
        # the cached factors, then the suffix's own rows causally
        a = _lowrank_attention(q, c, {"k": k, "v": v}, positions, start,
                               cfg)
        x = x + L.dense(lp["attn"]["wo"], a.astype(x.dtype))
        x, _ = _mlp(lp, x, cfg, None, None)

        # suffix K/V become the slot's dense tail; pad rows zeroed so
        # later folds see exactly what a full prefill would have left
        zmask = live_row[:, :, None, None]
        tk = jnp.where(zmask, k, 0).astype(cfg.jax_dtype)
        tv = jnp.where(zmask, v, 0).astype(cfg.jax_dtype)
        pad = [(0, 0), (0, tail_len - s), (0, 0), (0, 0)]
        return x, {"k": jnp.pad(tk, pad), "v": jnp.pad(tv, pad)}

    x, tails = L.xscan(scan_fn, x, (p["layers"], prefix))
    x_last = jnp.take_along_axis(
        x, jnp.maximum(slen - 1, 0)[:, None, None], axis=1)
    return T.logits_head(p, x_last, cfg)[:, 0], tails
