"""Unified per-family model API (used by launch/, serving/, tests/).

``model_fns(cfg)`` returns a ``ModelFns`` with a common signature across the
six families; ``input_specs(cfg, shape)`` builds ShapeDtypeStruct stand-ins
for every input of the requested workload kind (the dry-run pattern — no
device allocation ever happens for full configs).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, ShapeSpec
from . import encdec, hybrid, mamba2, moe, transformer, vlm

Array = jax.Array


def tokens_prefill_inputs(cfg, tokens, make, mem_len=None):
    """Default ``ModelFns.prefill_inputs``: the token matrix is the whole
    prefill input (dense, moe, ssm, hybrid)."""
    return (tokens,)


def no_batch_extras(cfg, b, s, make):
    """Default ``ModelFns.batch_extras``: tokens/labels are the whole
    training batch."""
    return {}


@dataclasses.dataclass(frozen=True)
class ModelFns:
    """Per-family model surface.

    ``prefill_inputs``/``batch_extras`` describe the family's EXTRA
    positional prefill inputs and training-batch members (vlm image
    embeddings, audio encoder frames) through one table, so every
    consumer — spec builders here, the serving engine's admission path —
    reads the same contract instead of growing its own ``cfg.family``
    if-chain (the per-family table drift ``splice_cache`` warns about).
    ``make(shape, dtype)`` is the leaf constructor: ShapeDtypeStruct for
    specs, ``jnp.zeros`` for the serving engine's placeholder inputs.
    """
    init: Callable                      # (key, cfg) -> params
    loss_fn: Callable                   # (params, cfg, batch) -> scalar
    prefill: Callable                   # (params, cfg, *inputs) -> (logits, cache)
    decode_step: Callable               # (params, cfg, token, cache, pos)
    init_cache: Callable                # (cfg, batch, max_len) -> cache
    forward: Optional[Callable] = None
    # (cfg, tokens, make, mem_len) -> positional prefill inputs
    prefill_inputs: Callable = tokens_prefill_inputs
    # (cfg, b, s, make) -> {name: leaf} extra training-batch members
    batch_extras: Callable = no_batch_extras


def run_decode_block(step: Callable, sampler: Callable, max_block: int,
                     tok: Array, cache, pos: Array, n_steps,
                     stop_table: Array, key, round0):
    """Bounded on-device multi-token decode loop — N steps, ONE dispatch.

    Every family's ``decode_step`` already has a scan-able signature (all
    array arguments, static shapes), so one loop serves them all:
    ``step(tok, cache, pos) -> (logits, cache)`` is the single-token fn
    closed over params/config (and any loop-invariant extras such as the
    decomposed cache's ``frozen_len``).

    The carry is ``(i, done_mask, last_tok, cache, pos, token_buf)``; the
    sampler runs ON DEVICE each iteration (``sampler(logits, 1)``, plus a
    per-round PRNG key ``fold_in(key, round0 + i)`` when the sampler
    declares ``takes_key = True`` — the host's single-step path folds the
    same round index, so stochastic sampling stays byte-identical across
    block sizes).  The loop exits EARLY the first step any slot emits one
    of its stop tokens (``stop_table`` int32 [B, W], −1-padded rows, one
    row per slot): stops can then only land on the final returned step, so
    the host's one-pass EOS/stop/budget bookkeeping at the block boundary
    replays the single-step engine's decisions exactly (slots free and
    admission retries happen at the same round they would have).

    Returns ``(token_buf [max_block, B], steps_done, done_mask, cache)``;
    rows of ``token_buf`` at or beyond ``steps_done`` are zeros.
    """
    takes_key = bool(getattr(sampler, "takes_key", False))
    b = tok.shape[0]
    buf0 = jnp.zeros((max_block, b), jnp.int32)
    done0 = jnp.zeros((b,), bool)
    n_steps = jnp.asarray(n_steps, jnp.int32)
    round0 = jnp.asarray(round0, jnp.int32)

    def cond(carry):
        i, done = carry[0], carry[1]
        return (i < n_steps) & ~done.any()

    def body(carry):
        i, _, tok, cache, pos, buf = carry
        logits, cache = step(tok, cache, pos)
        if takes_key:
            nxt = sampler(logits, 1, jax.random.fold_in(key, round0 + i))
        else:
            nxt = sampler(logits, 1)
        nxt = nxt.astype(jnp.int32)
        buf = jax.lax.dynamic_update_index_in_dim(buf, nxt, i, 0)
        done = (nxt[:, None] == stop_table).any(axis=1)
        return (i + 1, done, nxt, cache, pos + 1, buf)

    i, done, _, cache, _, buf = jax.lax.while_loop(
        cond, body, (jnp.int32(0), done0, tok, cache, pos, buf0))
    return buf, i, done, cache


_FAMILY = {
    "dense": ModelFns(transformer.init, transformer.loss_fn,
                      transformer.prefill, transformer.decode_step,
                      transformer.init_cache, transformer.forward),
    "moe": ModelFns(moe.init, moe.loss_fn, moe.prefill, moe.decode_step,
                    moe.init_cache, moe.forward),
    "ssm": ModelFns(mamba2.init, mamba2.loss_fn, mamba2.prefill,
                    mamba2.decode_step, mamba2.init_state, mamba2.forward),
    "hybrid": ModelFns(hybrid.init, hybrid.loss_fn, hybrid.prefill,
                       hybrid.decode_step, hybrid.init_state, hybrid.forward),
    "vlm": ModelFns(vlm.init, vlm.loss_fn, vlm.prefill, vlm.decode_step,
                    vlm.init_cache, vlm.forward,
                    prefill_inputs=vlm.prefill_inputs,
                    batch_extras=vlm.batch_extras),
    "audio": ModelFns(encdec.init, encdec.loss_fn, encdec.prefill,
                      encdec.decode_step, encdec.init_cache, encdec.forward,
                      prefill_inputs=encdec.prefill_inputs,
                      batch_extras=encdec.batch_extras),
}


def model_fns(cfg: ArchConfig) -> ModelFns:
    return _FAMILY[cfg.family]


def init_params(cfg: ArchConfig, seed: int = 0, mesh=None):
    """Seeded random params in ``cfg.dtype`` from ONE jitted program, so no
    float32 copy of a whole stacked weight is ever materialized (the eager
    init draws each [L, ...] weight in f32 before casting).  With a mesh
    the params are placed once, replicated over it (the key is made inside
    the program from the host-side seed, so nothing moves between
    devices)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = None if mesh is None else NamedSharding(mesh, P())
    init = jax.jit(lambda s: model_fns(cfg).init(jax.random.PRNGKey(s), cfg),
                   in_shardings=rep, out_shardings=rep)
    return init(seed)


# ---------------------------------------------------------------------------
# Cache splicing (per-slot admission support, every family)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def cache_batch_axes(cfg: ArchConfig):
    """Pytree (matching ``init_cache``'s structure) of each leaf's batch
    axis, derived by probing ``init_cache`` at two batch sizes — no
    per-family table to drift when a family adds a cache leaf.  The batch
    axis is NOT uniform across families (hybrid mamba state and vlm self
    KV carry leading group axes), which is why gang admission used to be
    the only safe policy for them."""
    fns = model_fns(cfg)
    a = jax.eval_shape(lambda: fns.init_cache(cfg, 2, 8))
    b = jax.eval_shape(lambda: fns.init_cache(cfg, 5, 8))

    def axis(x, y):
        d = [i for i, (m, n) in enumerate(zip(x.shape, y.shape)) if m != n]
        assert len(d) == 1, f"ambiguous batch axis for leaf {x.shape}"
        return d[0]

    return jax.tree_util.tree_map(axis, a, b)


def cache_shardings(cfg: ArchConfig, cache, mesh, seq_shard: bool = True):
    """NamedSharding tree for any family's decode cache (dense k/v AND the
    low-rank ``k_u``/``k_vt`` leaves); the serving engine places every
    cache it allocates through this (with ``seq_shard=False`` — slot-axis
    DP only).  Rules live in ``distributed.sharding.cache_pspec``."""
    from ..distributed import sharding as sh
    return sh.cache_sharding(cache, mesh, cfg, seq_shard=seq_shard)


def splice_cache(cfg: ArchConfig, old, new, slot_indices,
                 src_indices=None):
    """Scatter batch rows ``src_indices`` (default ``0…n−1``) of ``new``
    into ``old`` at ``slot_indices`` along each leaf's batch axis.  ``new``
    may carry more batch rows than ``len(slot_indices)`` (bucketed prefill
    padding); the excess rows are dropped.  Live slots' rows are untouched,
    so admission never re-prefills in-flight sequences — any family."""
    axes = cache_batch_axes(cfg)
    idx = jnp.asarray(slot_indices, jnp.int32)      # traced-input friendly
    src = jnp.arange(idx.shape[0], dtype=jnp.int32) \
        if src_indices is None else jnp.asarray(src_indices, jnp.int32)

    def one(o, nw, ax):
        om = jnp.moveaxis(o, ax, 0)
        nm = jnp.moveaxis(nw, ax, 0)[src].astype(o.dtype)
        return jnp.moveaxis(om.at[idx].set(nm), 0, ax)

    return jax.tree_util.tree_map(one, old, new, axes)


def tree_ready(tree) -> bool:
    """Non-blocking done-probe over a pytree of in-flight jax arrays.

    ``jax.Array.is_ready()`` asks the runtime whether the producing
    computation has finished WITHOUT synchronizing on it — this is the
    cheap fence the async serving engine polls at step boundaries to
    decide whether an in-flight prefill/Lanczos result can be spliced.
    Leaves without ``is_ready`` (numpy arrays, python scalars) count as
    ready."""
    for leaf in jax.tree_util.tree_leaves(tree):
        probe = getattr(leaf, "is_ready", None)
        if probe is not None and not probe():
            return False
    return True


def splice_on_ready(cfg: ArchConfig, old, new, slot_indices,
                    src_indices=None):
    """Splice-if-done: returns ``splice_cache(...)`` when every leaf of
    ``new`` is ready (its producing prefill has finished on device), or
    ``None`` — meaning "not yet, keep decoding" — without blocking.
    The async engine's ticket pool is built on this entry point's
    probe+splice pairing."""
    if not tree_ready(new):
        return None
    return splice_cache(cfg, old, new, slot_indices, src_indices)


@dataclasses.dataclass(frozen=True)
class DecomposedFns:
    """Decomposed-execution surface, bound to ONE DecomposeEngine.

    ``forward``/``logit_kl`` run policy-selected decomposed blocks;
    ``prefill_dkv``/``decode_step_dkv``/``compress_tail`` are the
    decomposed-KV-cache serving path.  Obtain via :func:`decomposed_fns`.
    """
    engine: Any
    forward: Callable               # (params, tokens) -> logits
    logit_kl: Callable              # (params, tokens) -> scalar
    prefill_dkv: Callable           # (params, tokens, rank, ...) -> (logits, cache)
    decode_step_dkv: Callable       # (params, token, cache, pos, frozen_len)
    compress_tail: Callable         # (cache, rank[, frozen_len, fold]) -> cache
    splice_dkv: Callable = None     # (live, fresh, slot_indices) -> cache


def decomposed_fns(cfg: ArchConfig, engine) -> DecomposedFns:
    """Bind the decomposed-execution entry points to ``engine``.

    The engine (a ``repro.engine.DecomposeEngine``) is the ONLY source of
    decomposition for everything returned here — consumers never touch
    ranks, hooks, or backends directly.  The decomposed-KV entries take
    any config that ``decomposed_kv.unsupported`` passes; the
    decomposed-activation ones (``forward``/``logit_kl``) run the dense
    transformer only.
    """
    from . import decomposed as D
    from . import decomposed_kv as DK
    why = DK.unsupported(cfg)
    if why is not None:
        raise ValueError(f"decomposed execution: {why}")
    runtime = D.DecomposedRuntime(engine=engine) \
        if engine.config.policy is not None else None

    def forward(params, tokens, wfactors=None):
        assert cfg.family == "dense", "decomposed activations: dense family"
        assert runtime is not None, "engine has no decomposition policy"
        return D.forward(params, cfg, tokens, runtime, wfactors)

    def logit_kl(params, tokens, wfactors=None):
        assert cfg.family == "dense", "decomposed activations: dense family"
        assert runtime is not None, "engine has no decomposition policy"
        return D.logit_kl(params, cfg, tokens, runtime, wfactors)

    def prefill_dkv(params, tokens, rank=None, tail=None, exact=False):
        return DK.prefill_dkv(
            params, cfg, tokens,
            engine.config.kv_rank if rank is None else rank,
            tail=engine.config.kv_tail if tail is None else tail,
            exact=exact, engine=engine)

    def decode_step_dkv(params, token, cache, pos, frozen_len):
        return DK.decode_step_dkv(params, cfg, token, cache, pos, frozen_len)

    def compress_tail(cache, rank=None, frozen_len=None, fold=None):
        return DK.compress_tail(
            cache, cfg, engine.config.kv_rank if rank is None else rank,
            frozen_len=frozen_len, fold=fold)

    return DecomposedFns(engine, forward, logit_kl, prefill_dkv,
                         decode_step_dkv, compress_tail, DK.splice_dkv)


def abstract_params(cfg: ArchConfig):
    """Parameter ShapeDtypeStructs without allocating anything."""
    fns = model_fns(cfg)
    return jax.eval_shape(lambda k: fns.init(k, cfg), jax.random.PRNGKey(0))


def param_count(cfg: ArchConfig) -> int:
    import math
    shapes = abstract_params(cfg)
    return sum(math.prod(l.shape)
               for l in jax.tree_util.tree_leaves(shapes))


def active_param_count(cfg: ArchConfig) -> int:
    """Matmul-active params per token for the 6·N·D MODEL_FLOPS convention:
    MoE counts top_k of num_experts; the input embedding is excluded when
    untied (pure gather — no FLOPs), kept once when tied (it IS the head)."""
    total = param_count(cfg)
    if not cfg.tie_embeddings:
        total -= cfg.padded_vocab * cfg.d_model      # gather-only embed table
    if not cfg.num_experts:
        return total
    n_moe_layers = cfg.num_layers - cfg.first_k_dense
    expert_params = 3 * cfg.d_model * cfg.moe_d_ff
    inactive = n_moe_layers * (cfg.num_experts - cfg.top_k) * expert_params
    return total - inactive


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins, per workload kind)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_specs(cfg: ArchConfig, shape: ShapeSpec) -> Dict[str, Any]:
    b, s = shape.global_batch, shape.seq_len
    specs = {"tokens": _sds((b, s), jnp.int32),
             "labels": _sds((b, s), jnp.int32)}
    specs.update(model_fns(cfg).batch_extras(cfg, b, s, _sds))
    return specs


def prefill_input_specs(cfg: ArchConfig, shape: ShapeSpec):
    """Positional inputs of fns.prefill after (params, cfg)."""
    b, s = shape.global_batch, shape.seq_len
    tokens = _sds((b, s), jnp.int32)
    return model_fns(cfg).prefill_inputs(cfg, tokens, _sds, mem_len=s)


def decode_input_specs(cfg: ArchConfig, shape: ShapeSpec):
    """(token, cache, pos) specs for fns.decode_step."""
    b, s = shape.global_batch, shape.seq_len
    fns = model_fns(cfg)
    cache = jax.eval_shape(lambda: fns.init_cache(cfg, b, s))
    return (_sds((b,), jnp.int32), cache, _sds((b,), jnp.int32))


def make_fake_batch(cfg: ArchConfig, shape: ShapeSpec, key=None
                    ) -> Dict[str, Array]:
    """Concrete synthetic batch matching train_batch_specs (smoke/examples)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    specs = train_batch_specs(cfg, shape)
    out: Dict[str, Array] = {}
    for name, sp in sorted(specs.items()):
        key, sub = jax.random.split(key)
        if jnp.issubdtype(sp.dtype, jnp.integer):
            out[name] = jax.random.randint(sub, sp.shape, 0, cfg.vocab,
                                           sp.dtype)
        else:
            out[name] = jax.random.normal(sub, sp.shape, jnp.float32) \
                .astype(sp.dtype)
    return out
