"""Token-choice top-k MoE (olmoe-1b-7b, kimi-k2-1t-a32b).

Dispatch is sort-based with static capacity buffers — the GSPMD-provable
formulation (einsum expert matmuls over [E, C, H] buffers; scatter/gather
carry no FLOPs):

  1. router top-k → (expert_id, gate) per token-slot,
  2. rank-in-expert via sorted-run arithmetic (no [T·k, E] one-hot cumsum),
  3. token indices scattered into an [E, C] slot table (overflow drops — the
     classic capacity-factor semantics),
  4. expert FFN as one batched einsum over [E, C, H] (E shards over "model"
     = expert parallelism; GSPMD inserts the dispatch/combine collectives),
  5. combine = gather + gate-weighted sum over the k slots.

kimi-k2 extras: ``first_k_dense`` leading dense blocks and
``n_shared_experts`` always-on shared expert(s) added to the MoE output.

A config with ``router_experts`` (mellum2-12b) holds a share of the experts
and runs :func:`held_experts_ffn` instead: it routes over the router's full
width, computes the gated outputs of the experts this chip holds, and drops
no token — sorted token→expert runs through a grouped matmul
(``jax.lax.ragged_dot``), so a token's output does not depend on what it
was batched with.  Such configs may also mix window and full-attention
layers (``cfg.layer_kinds``); the dense-KV path here then masks each
layer's window and uses its kind's RoPE.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import transformer as T

Array = jax.Array
Params = Dict[str, Any]

AUX_LOSS_COEF = 0.01

# Explicit-EP mesh (set by launch.dryrun / launch.train before tracing).
# When not None, moe_ffn routes through the shard_map expert-parallel path
# (moe_ffn_shard_map) instead of the GSPMD formulation — the §Perf fix for
# GSPMD replicating the [E, C, H] dispatch buffer (see EXPERIMENTS.md).
SHARD_MAP_MESH = None


# ---------------------------------------------------------------------------
# MoE FFN
# ---------------------------------------------------------------------------

def moe_ffn_init(key, cfg) -> Params:
    ks = jax.random.split(key, 5)
    dt = cfg.jax_dtype
    e, h, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    scale = h ** -0.5

    def w(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    p = {
        "router": {"w": w(ks[0], (h, cfg.router_width))},
        "w_gate": w(ks[1], (e, h, f)),
        "w_up": w(ks[2], (e, h, f)),
        "w_down": (jax.random.normal(ks[3], (e, f, h), jnp.float32)
                   * f ** -0.5).astype(dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = L.mlp_init(ks[4], h, f * cfg.n_shared_experts, dt,
                                 cfg.gated_mlp)
    return p


def moe_ffn(p: Params, x: Array, cfg) -> Tuple[Array, Array]:
    """x [B, S, H] → (y [B, S, H], aux_loss scalar)."""
    if SHARD_MAP_MESH is not None:
        return moe_ffn_shard_map(p, x, cfg, SHARD_MAP_MESH)
    b, s, h = x.shape
    e, k = cfg.num_experts, cfg.top_k
    t = b * s
    cap = max(1, math.ceil(t * k * cfg.capacity_factor / e))

    xf = x.reshape(t, h)
    logits = jnp.einsum("th,he->te", xf.astype(jnp.float32), p["router"]["w"]
                        .astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    gate_vals, eidx = jax.lax.top_k(probs, k)                  # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # Load-balancing auxiliary loss (Switch-style).
    me = jnp.mean(probs, axis=0)                               # [E]
    ce = jnp.mean(jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce) * AUX_LOSS_COEF

    # ---- rank-in-expert via sorted runs --------------------------------
    slots_e = eidx.reshape(t * k)                              # [T·k]
    slot_tok = jnp.arange(t * k, dtype=jnp.int32) // k
    order = jnp.argsort(slots_e)
    sorted_e = slots_e[order]
    counts = jnp.bincount(slots_e, length=e)                   # [E]
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank_sorted = jnp.arange(t * k, dtype=jnp.int32) - starts[sorted_e]
    inv = jnp.argsort(order)
    rank = rank_sorted[inv]                                    # [T·k]

    # ---- dispatch: slot table then gather -------------------------------
    slot_table = jnp.full((e, cap), t, jnp.int32)              # t = OOB row
    slot_table = slot_table.at[slots_e, rank].set(slot_tok, mode="drop")
    x_pad = jnp.concatenate([xf, jnp.zeros((1, h), xf.dtype)], axis=0)
    buf = x_pad[slot_table]                                    # [E, C, H]

    # ---- expert FFN (EP einsum) -----------------------------------------
    act = L.activation_fn(cfg.activation)
    hidden = act(jnp.einsum("ech,ehf->ecf", buf, p["w_gate"])) \
        * jnp.einsum("ech,ehf->ecf", buf, p["w_up"])
    out_buf = jnp.einsum("ecf,efh->ech", hidden, p["w_down"])  # [E, C, H]

    # ---- combine ---------------------------------------------------------
    in_cap = (rank < cap)
    y_slots = out_buf[slots_e, jnp.minimum(rank, cap - 1)]     # [T·k, H]
    y_slots = jnp.where(in_cap[:, None], y_slots, 0.0)
    y = jnp.sum(y_slots.reshape(t, k, h)
                * gate_vals.astype(y_slots.dtype)[..., None], axis=1)
    y = y.reshape(b, s, h).astype(x.dtype)

    if "shared" in p:
        y = y + L.mlp(p["shared"], x, cfg.activation)
    return y, aux


def route(p: Params, xf: Array, cfg) -> Tuple[Array, Array, Array]:
    """Softmax router over its full width, top-k, gates renormalized over
    the k: xf [T, H] → (gates [T, k] f32, expert ids [T, k], probs [T, E])."""
    logits = jnp.einsum("th,he->te", xf.astype(jnp.float32),
                        p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, eidx, probs


def held_experts_ffn(p: Params, x: Array, cfg, layer: Optional[Array] = None
                     ) -> Tuple[Array, Array, Array]:
    """The held experts' share of the expert layer, dropping nothing.

    x [B, S, H] → (y [B, S, H], aux loss, expert ids [B·S, k]).  Each of
    the T·k token→expert picks whose expert this chip holds
    (``expert_first`` … ``+ num_experts``) is sorted into its expert's
    run; the picks of experts held elsewhere sort after every run and are
    computed by no one here.  Gate, up and down are grouped matmuls over
    the runs (``jax.lax.ragged_dot``; rows past the last run belong to
    no group and give zeros), and each token sums its held
    picks' outputs times their gates.  Everything is in the named scope
    ``dcom.moe``.

    With ``layer`` (a traced int) ``p["w_gate"|"w_up"|"w_down"]`` are the
    whole stacks of every layer, [L, held, …] or [L·held, …], and the
    layer's runs sit at groups ``layer·held`` … ``+ held``, every other
    group empty.  A layer scan passes the stacks as loop constants this
    way because the grouped matmul is a custom call that takes no fused
    slice: a stack sliced per layer would be copied out before every
    call.  Without ``layer`` the weights are one layer's, at offset 0."""
    with jax.named_scope("dcom.moe"):
        b, s, h = x.shape
        t, k, held = b * s, cfg.top_k, cfg.num_experts
        xf = x.reshape(t, h)
        gates, eidx, probs = route(p, xf, cfg)
        e = cfg.router_width
        ce = jnp.mean(jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32), 0)
        aux = e * jnp.sum(jnp.mean(probs, axis=0) * ce) * AUX_LOSS_COEF

        w_gate, w_up, w_down = (p[w].reshape((-1,) + p[w].shape[-2:])
                                for w in ("w_gate", "w_up", "w_down"))
        groups = w_gate.shape[0]                     # held, or L·held
        first = 0 if layer is None else layer * held
        local = (eidx - cfg.expert_first).reshape(t * k)
        mine = (local >= 0) & (local < held)
        run = jnp.where(mine, first + local, groups)  # away picks sort last
        order = jnp.argsort(run, stable=True)
        sizes = jnp.bincount(run, length=groups + 1)[:groups].astype(
            jnp.int32)
        # [T·k, H] in run order.  A per-row dynamic index, not the plain
        # row gather xf[order // k]: for that one the TPU compiler can ask
        # more scoped VMEM than its limit and refuse the program (the
        # prefill of one 1536-token prompt at mellum2-12b's widths).
        rows = jax.vmap(lambda i: jax.lax.dynamic_index_in_dim(
            xf, i, keepdims=False))(order // k)
        act = L.activation_fn(cfg.activation)
        hid = act(jax.lax.ragged_dot(rows, w_gate, sizes)) \
            * jax.lax.ragged_dot(rows, w_up, sizes)
        out = jax.lax.ragged_dot(hid, w_down, sizes)          # [T·k, H]
        out = out[jnp.argsort(order)].astype(jnp.float32)     # pick order
        g = jnp.where(mine, gates.reshape(t * k), 0.0)
        y = jnp.sum(jnp.where(mine[:, None], out, 0.0).reshape(t, k, h)
                    * g.reshape(t, k, 1), axis=1)
        y = y.reshape(b, s, h).astype(x.dtype)
        if "shared" in p:
            y = y + L.mlp(p["shared"], x, cfg.activation)
    return y, aux, eidx


def expert_layer(p: Params, x: Array, cfg) -> Tuple[Array, Array]:
    """The config's expert layer: the drop-free held share when the config
    names its router width, else the capacity-factor ``moe_ffn``."""
    if cfg.router_experts:
        y, aux, _ = held_experts_ffn(p, x, cfg)
        return y, aux
    return moe_ffn(p, x, cfg)


# ---------------------------------------------------------------------------
# Explicit expert-parallel path (shard_map)
# ---------------------------------------------------------------------------
# Why: under pure GSPMD, scatter/gather between data-sharded tokens and the
# model-sharded [E, C, H] capacity buffer lowers to zero-pad + full-buffer
# all-reduce (~150 GB/layer at kimi scale — measured 10.8 TB/step/device).
# With shard_map the structure is explicit and nearly collective-free:
#   * activations are data-sharded and model-REPLICATED, so every model
#     shard already holds the tokens it needs — dispatch is local;
#   * each model shard builds buffers only for its own E/TP experts;
#   * 2-D ("expert_sharding=2d") weights all_gather their F shards over
#     "data" (FSDP-style, the unavoidable 1T-model term);
#   * combine is one psum over "model" of the gate-weighted outputs.
# Capacity becomes per-(data-shard, expert) — same expected load, documented
# semantic difference vs the global-capacity GSPMD path.

def moe_ffn_shard_map(p: Params, x: Array, cfg, mesh) -> Tuple[Array, Array]:
    from jax.sharding import PartitionSpec as P
    e, k, h = cfg.num_experts, cfg.top_k, cfg.d_model
    two_d = getattr(cfg, "expert_sharding", "1d") == "2d"
    tp = mesh.shape["model"]
    dp_names = ("pod", "data") if "pod" in mesh.axis_names else ("data",)

    def inner(xl, router_w, wg, wu, wd):
        b, s, _ = xl.shape
        t = b * s
        e_loc = wg.shape[0]
        cap = max(1, math.ceil(t * k * cfg.capacity_factor / e))
        m_idx = jax.lax.axis_index("model")

        xf = xl.reshape(t, h)
        logits = jnp.einsum("th,he->te", xf.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, eidx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32),
                      axis=0)
        aux = e * jnp.sum(me * ce) * AUX_LOSS_COEF
        aux = jax.lax.pmean(aux, dp_names)

        # local rank-in-expert (global expert ids, local tokens)
        slots_e = eidx.reshape(t * k)
        slot_tok = jnp.arange(t * k, dtype=jnp.int32) // k
        order = jnp.argsort(slots_e)
        counts = jnp.bincount(slots_e, length=e)
        starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        rank_sorted = jnp.arange(t * k, dtype=jnp.int32) \
            - starts[slots_e[order]]
        rank = rank_sorted[jnp.argsort(order)]

        # keep only this model shard's experts; OOB rows drop
        local_e = slots_e - m_idx * e_loc
        owned = (local_e >= 0) & (local_e < e_loc) & (rank < cap)
        le = jnp.where(owned, local_e, e_loc)
        rk = jnp.where(owned, rank, cap)
        slot_table = jnp.full((e_loc, cap), t, jnp.int32)
        slot_table = slot_table.at[le, rk].set(slot_tok, mode="drop")
        x_pad = jnp.concatenate([xf, jnp.zeros((1, h), xf.dtype)], axis=0)
        buf = x_pad[slot_table]                          # [E_loc, C, H]

        if two_d:                                        # FSDP F-gather
            wg = jax.lax.all_gather(wg, "data", axis=2, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=2, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=1, tiled=True)

        act = L.activation_fn(cfg.activation)
        hidden = act(jnp.einsum("ech,ehf->ecf", buf, wg)) \
            * jnp.einsum("ech,ehf->ecf", buf, wu)
        out_buf = jnp.einsum("ecf,efh->ech", hidden, wd)  # [E_loc, C, H]

        y_slots = out_buf[jnp.minimum(le, e_loc - 1),
                          jnp.minimum(rk, cap - 1)]
        y_slots = jnp.where(owned[:, None], y_slots, 0.0)
        y = jnp.sum(y_slots.reshape(t, k, h)
                    * gate_vals.astype(y_slots.dtype)[..., None], axis=1)
        # local gate-weighted sum accumulates fp32; the cross-shard combine
        # rides bf16 (halves the psum payload — A2 in EXPERIMENTS.md §Perf;
        # ≤ TP-width shards summed, bf16 is the production norm).
        y = jax.lax.psum(y.astype(xl.dtype), "model")
        return y.reshape(b, s, h), aux

    dp = dp_names if len(dp_names) > 1 else dp_names[0]
    w_f_spec = "data" if two_d else None
    out, aux = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  P("model", None, w_f_spec), P("model", None, w_f_spec),
                  P("model", w_f_spec, None)),
        out_specs=(P(dp, None, None), P()),
    )(x, p["router"]["w"], p["w_gate"], p["w_up"], p["w_down"])
    if "shared" in p:
        out = out + L.mlp(p["shared"], x, cfg.activation)
    return out, aux


# ---------------------------------------------------------------------------
# Blocks / model
# ---------------------------------------------------------------------------

def init_moe_block(key, cfg) -> Params:
    ks = jax.random.split(key, 2)
    dt = cfg.jax_dtype
    return {
        "attn_norm": L.norm_init(cfg.d_model, dt),
        "attn": L.attention_init(ks[0], cfg.d_model, cfg.num_heads,
                                 cfg.num_kv_heads, cfg.resolved_head_dim, dt),
        "mlp_norm": L.norm_init(cfg.d_model, dt),
        "moe": moe_ffn_init(ks[1], cfg),
    }


def moe_block(p: Params, x: Array, positions: Array, cfg,
              kind=None) -> Tuple[Array, Array]:
    x = x + L.causal_attention(p["attn"], L.rmsnorm(p["attn_norm"], x,
                                                    cfg.norm_eps),
                               cfg, positions, kind=kind)
    y, aux = expert_layer(p["moe"], L.rmsnorm(p["mlp_norm"], x,
                                              cfg.norm_eps), cfg)
    return x + y, aux


def _kinds(cfg, n_layers: int):
    """Scan inputs of each expert layer's attention kind (None where every
    layer is plain full attention, so that scan is unchanged)."""
    if not cfg.sliding_window and not cfg.yarn_factor:
        return None
    return L.layer_kinds_table(
        cfg, range(cfg.num_layers - n_layers, cfg.num_layers))


def init(key, cfg) -> Params:
    ks = jax.random.split(key, 4)
    dt = cfg.jax_dtype
    nd, nm = cfg.first_k_dense, cfg.num_layers - cfg.first_k_dense
    p: Params = {
        "embed": L.embed_init(ks[0], cfg.padded_vocab, cfg.d_model, dt),
        "layers": jax.vmap(lambda k: init_moe_block(k, cfg))(
            jax.random.split(ks[1], nm)),
        "final_norm": L.norm_init(cfg.d_model, dt),
        "lm_head": L.dense_init(ks[2], cfg.d_model, cfg.padded_vocab, dt),
    }
    if nd:
        p["dense_layers"] = jax.vmap(lambda k: T.init_block(k, cfg))(
            jax.random.split(ks[3], nd))
    return p


def forward(p: Params, cfg, tokens: Array) -> Tuple[Array, Array]:
    """tokens [B, S] → (logits, aux_loss)."""
    x = p["embed"]["w"][tokens]
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)

    if "dense_layers" in p:
        dense_body = L.ckpt(T.block, cfg, static_argnums=(3,))
        x, _ = L.xscan(
            lambda x, lp: (dense_body(lp, x, positions, cfg), None),
            x, p["dense_layers"])

    body = L.ckpt(moe_block, cfg, static_argnums=(3,))
    kinds = _kinds(cfg, cfg.num_layers - cfg.first_k_dense)

    def scan_fn(x, inp):
        lp, kind = inp
        x, aux = body(lp, x, positions, cfg, kind)
        return x, aux

    x, auxs = L.xscan(scan_fn, x, (p["layers"], kinds))
    logits = T.logits_head(p, x, cfg)
    return logits, jnp.sum(auxs)


def loss_fn(p: Params, cfg, batch: Dict[str, Array]) -> Array:
    logits, aux = forward(p, cfg, batch["tokens"])
    return L.cross_entropy(logits, batch["labels"]) + aux


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int) -> Params:
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    nd, nm = cfg.first_k_dense, cfg.num_layers - cfg.first_k_dense
    c = {"moe": {"k": jnp.zeros((nm, batch, max_len, kvh, hd), cfg.jax_dtype),
                 "v": jnp.zeros((nm, batch, max_len, kvh, hd), cfg.jax_dtype)}}
    if nd:
        c["dense"] = {
            "k": jnp.zeros((nd, batch, max_len, kvh, hd), cfg.jax_dtype),
            "v": jnp.zeros((nd, batch, max_len, kvh, hd), cfg.jax_dtype)}
    return c


def prefill(p: Params, cfg, tokens: Array, max_len: Optional[int] = None
            ) -> Tuple[Array, Params]:
    b, s = tokens.shape
    t = max_len or s
    x = p["embed"]["w"][tokens]
    positions = jnp.broadcast_to(jnp.arange(s), tokens.shape)
    pad = [(0, 0), (0, t - s), (0, 0), (0, 0)]
    cache: Params = {}

    def kv_of(lp, x, kind=None):
        freqs, scale, _ = kind if kind is not None else (None, 1.0, 0)
        h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
        k = L.apply_rope(L._split_heads(L.dense(lp["attn"]["wk"], h),
                                        cfg.num_kv_heads), positions,
                         cfg.rope_theta, freqs, scale)
        v = L._split_heads(L.dense(lp["attn"]["wv"], h), cfg.num_kv_heads)
        return {"k": jnp.pad(k.astype(cfg.jax_dtype), pad),
                "v": jnp.pad(v.astype(cfg.jax_dtype), pad)}

    if "dense_layers" in p:
        def scan_d(x, lp):
            kv = kv_of(lp, x)
            return T.block(lp, x, positions, cfg), kv
        x, cache["dense"] = L.xscan(scan_d, x, p["dense_layers"])

    def scan_m(x, inp):
        lp, kind = inp
        kv = kv_of(lp, x, kind)
        x, _ = moe_block(lp, x, positions, cfg, kind)
        return x, kv

    kinds = _kinds(cfg, cfg.num_layers - cfg.first_k_dense)
    x, cache["moe"] = L.xscan(scan_m, x, (p["layers"], kinds))
    logits = T.logits_head(p, x[:, -1:, :], cfg)[:, 0]
    return logits, cache


def decode_step(p: Params, cfg, token: Array, cache: Params, pos: Array
                ) -> Tuple[Array, Params]:
    x = p["embed"]["w"][token][:, None, :]
    new_cache: Params = {}

    if "dense_layers" in p:
        def scan_d(x, inp):
            lp, c = inp
            h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
            a, c = L.decode_attention(lp["attn"], h, c, pos, cfg)
            x = x + a
            x = x + L.mlp(lp["mlp"], L.rmsnorm(lp["mlp_norm"], x,
                                               cfg.norm_eps), cfg.activation)
            return x, c
        x, new_cache["dense"] = L.xscan(scan_d, x,
                                             (p["dense_layers"],
                                              cache["dense"]))

    def scan_m(x, inp):
        lp, c, kind = inp
        h = L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
        a, c = L.decode_attention(lp["attn"], h, c, pos, cfg, kind)
        x = x + a
        y, _ = expert_layer(lp["moe"], L.rmsnorm(lp["mlp_norm"], x,
                                                 cfg.norm_eps), cfg)
        return x + y, c

    kinds = _kinds(cfg, cfg.num_layers - cfg.first_k_dense)
    x, new_cache["moe"] = L.xscan(scan_m, x, (p["layers"], cache["moe"],
                                              kinds))
    return T.logits_head(p, x, cfg)[:, 0], new_cache
