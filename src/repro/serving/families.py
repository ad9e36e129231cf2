"""ServingFamily — the per-family protocol behind ``serving.Engine``.

PRs 2–8 built the production serving stack (per-slot continuous
batching, paged KV + prefix reuse, fused decode blocks, async prefill)
hardcoded to the transformer decomposed-KV family.  This module extracts
everything the engine used to special-case into one protocol, and the
engine dispatches EXCLUSIVELY through it (dcomlint rule F1 gates any
``cfg.family`` branch creeping back into ``serving/__init__.py``):

* cache lifecycle — :meth:`ServingFamily.alloc` (allocation + mesh
  placement + sharding specs), :meth:`~ServingFamily.free_slot`;
* admission — :meth:`~ServingFamily.reserve` (capacity check, paged
  prefix lookups), :meth:`~ServingFamily.dispatch` (per-slot splice
  admission as :class:`PrefillTicket`\\ s), :meth:`~ServingFamily.gang`
  (the legacy whole-batch policy), and the prefill-cost hook the
  :class:`~repro.serving.Scheduler` buckets on;
* decode — :meth:`~ServingFamily.decode` (single step) and
  :meth:`~ServingFamily.decode_block` (fused on-device loop);
* folds — :meth:`~ServingFamily.maybe_fold` / ``fold_horizon`` (no-ops
  for O(1)-state families: there is nothing to compress).

Registered families:

* ``transformer-dkv`` — the decomposed-KV path (slab or paged), byte-
  identical to the pre-protocol engine; selected whenever the engine is
  built with ``decompose_kv_rank``.
* ``dense`` — plain dense-KV transformer serving.  The only family whose
  gang admission may splice into a live cache (``gang_live_splice``).
* ``moe`` — dense KV serving with the expert-parallel ``moe_ffn`` path:
  routing/capacity live inside the model fns, and per-expert sharding
  comes from ``distributed.sharding``'s leaf rules under a mesh.  The
  serving engine never touches ``moe.SHARD_MAP_MESH`` — GSPMD partitions
  ``moe_ffn`` from the cache/param shardings alone.
* ``ssm`` (Mamba2) — conv_cache + ssm_state are fixed-size STATE SLOTS:
  no time axis, so folds are no-ops and a slot's memory never grows.
* ``hybrid`` (Zamba2-style) — composes per layer: attention layers carry
  sliced KV, mamba layers carry state slots; ``api.cache_batch_axes``
  probes each leaf's slot axis so one splice path serves the mixed tree.
* ``vlm`` / ``audio`` — dense-KV serving whose prefill carries extra
  modality inputs (``ModelFns.prefill_inputs``) and whose admission cost
  exceeds the token count (image tokens / encoder frames) — reflected in
  :meth:`~ServingFamily.prefill_cost` so scheduler bucketing tracks
  actual prefill work.

All mutable serving state (``cache``, ``pager``, ``pos``,
``frozen_len``, ``rank_eff``, ``live``) stays on the Engine — families
are stateless strategy objects holding only jitted callables, so tests
and benchmarks keep poking engine attributes directly.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..engine import DecomposeEngine, EngineConfig
from ..models import api
from ..obs import phase_scope

Array = jax.Array


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


@dataclasses.dataclass
class PrefillTicket:
    """One in-flight admission launch (the prefill side of the P/D split).

    Created at DISPATCH time: the prefill (forward + Lanczos, or a
    prefix-hit suffix pass) has been launched on device, the target slots
    are reserved, and — paged mode — the pages are already allocated and
    the prefix-hit refs held, so nothing the decode loop does during the
    async window can invalidate the launch.  ``probe`` is the result tree
    (``api.tree_ready`` gives a non-blocking done check); ``complete``
    materializes the results (splice + first-token sample — the only
    blocking point) and ``cancel`` unwinds the reservation (slots free,
    pages/refs release) without ever blocking on the device.
    """
    requests: List[Any]
    slots: List[int]
    plen: int
    probe: Any                       # pytree of in-flight jax arrays
    complete: Callable               # () -> (first_tokens, frozen_lens)
    cancel: Callable                 # () -> None (release pages/refs)
    t_dispatch: float = 0.0
    span: Any = None                 # obs.Span on the "tickets" track

    def ready(self) -> bool:
        return api.tree_ready(self.probe)


def _constrain(mesh):
    """Cache-tree ``with_sharding_constraint`` closure for the jitted step
    fns (identity when ``mesh`` is None — the single-device path traces the
    exact pre-mesh graph).  ``seq_shard=False``: the batch-1 time-axis
    ("flash-decoding") rule is for global-batch-1 long-context decode, not
    serving — a freshly prefilled single-request cache must stay replicated
    until spliced, not bounce through a sequence reshard per admission."""
    if mesh is None:
        return lambda c: c
    from ..distributed import sharding as sh
    return lambda c: sh.constrain_cache(c, mesh, seq_shard=False)


# ---------------------------------------------------------------------------
# Jitted step builders (lru-shared across Engine instances)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jitted_steps(fns: api.ModelFns, cfg: ArchConfig, max_len: int,
                  mesh=None):
    """Jitted (decode, prefill) shared across Engine instances of the same
    (config, mesh) — XLA executables are reused instead of re-traced per
    engine.  Under a mesh both the incoming and outgoing cache trees are
    sharding-constrained to ``distributed.sharding.cache_pspec``, so GSPMD
    keeps every per-slot update device-local along the batch axis.  The
    decode cache is DONATED: the engine rebinds ``self.cache`` at the call
    site, so the update writes in place."""
    con = _constrain(mesh)

    def decode(p, t, c, pos):
        lg, nc = fns.decode_step(p, cfg, t, con(c), pos)
        return lg, con(nc)

    def prefill(p, *a):
        lg, c = fns.prefill(p, cfg, *a, max_len)
        return lg, con(c)

    return jax.jit(decode, donate_argnums=(2,)), jax.jit(prefill)


@functools.lru_cache(maxsize=None)
def _jitted_dkv_decode(cfg: ArchConfig, mesh=None):
    from ..models import decomposed_kv as DK
    con = _constrain(mesh)

    def step(p, t, c, pos, fl):
        lg, nc = DK.decode_step_dkv(p, cfg, t, con(c), pos, frozen_len=fl)
        return lg, con(nc)

    return jax.jit(step, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _jitted_decode_block(fns: api.ModelFns, cfg: ArchConfig, block: int,
                         sampler, mesh=None):
    """Fused decode block for ANY family (dense path included): ``block``
    is the static loop bound, the actual step count per call is traced.
    lru-keyed on (fns, cfg, block, sampler, mesh) so equivalently
    configured engines share one executable; the cache carry is donated."""
    con = _constrain(mesh)

    def run(p, t, c, pos, n, stops, key, r0):
        step = lambda tk, cc, ps: fns.decode_step(p, cfg, tk, cc, ps)
        buf, steps, done, nc = api.run_decode_block(
            step, sampler, block, t, con(c), pos, n, stops, key, r0)
        return buf, steps, done, con(nc)

    return jax.jit(run, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _jitted_dkv_decode_block(cfg: ArchConfig, block: int, sampler,
                             mesh=None):
    from ..models import decomposed_kv as DK
    con = _constrain(mesh)

    def run(p, t, c, pos, fl, n, stops, key, r0):
        buf, steps, done, nc = DK.decode_block_dkv(
            p, cfg, t, con(c), pos, fl, n, stops, key, r0,
            sampler=sampler, max_block=block)
        return buf, steps, done, con(nc)

    return jax.jit(run, donate_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _jitted_dkv_prefill(cfg: ArchConfig, backend: str, expansion: int,
                        rank: int, tail: int, iters_extra: int,
                        exact: bool, mesh=None):
    """Jitted decomposed-KV prefill (forward + Lanczos/SVD factorization in
    ONE compiled program — ~100× over the eager path on small configs).
    Keyed on the decomposition-relevant engine knobs so equivalently
    configured serving engines share executables.  With a mesh the inner
    DecomposeEngine runs the factorization DP-sharded over the
    layers×batch axis and the fresh cache comes out sharding-constrained."""
    from ..models import decomposed_kv as DK
    eng = DecomposeEngine(EngineConfig(
        backend=backend, expansion=expansion, kv_rank=rank, kv_tail=tail,
        kv_iters_extra=iters_extra, mesh=mesh))
    con = _constrain(mesh)

    def prefill(p, tk):
        # (logits, cache[, expert picks]) — see DK.prefill_dkv
        lg, c, *picks = DK.prefill_dkv(p, cfg, tk, rank, tail=tail,
                                       exact=exact, engine=eng)
        return (lg, con(c), *picks)

    return jax.jit(prefill)


@functools.lru_cache(maxsize=None)
def _jitted_dkv_compress(cfg: ArchConfig, rank: int, mesh=None):
    # The incoming cache is donated: a fold GROWS the time axis, so only
    # the same-shaped leaves (tail, factors) alias — the rest is the
    # "not usable" warning filtered at serving import.
    from ..models import decomposed_kv as DK
    con = _constrain(mesh)
    return jax.jit(lambda c, fl, fm, nf: con(DK.compress_tail(
        con(c), cfg, rank, frozen_len=fl, fold=fm, new_frozen=nf)),
        donate_argnums=(0,))


@functools.lru_cache(maxsize=None)
def _jitted_splices(mesh=None):
    """Jitted cache-splice kernels (slot/src index vectors are traced, so
    one executable serves every admission with the same shape profile).
    The LIVE side keeps its batch sharding — and is donated, since every
    call site rebinds the engine cache to the splice result; the fresh
    side is typically smaller than the slot batch and stays wherever
    prefill left it."""
    from ..models import decomposed_kv as DK
    con = _constrain(mesh)
    dkv = jax.jit(lambda live, fresh, idx, src:
                  con(DK.splice_dkv(con(live), fresh, idx, src)),
                  donate_argnums=(0,))
    fam = jax.jit(lambda old, new, idx, src, cfg:
                  con(api.splice_cache(cfg, con(old), new, idx, src)),
                  static_argnums=(4,), donate_argnums=(0,))
    return dkv, fam


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, type] = {}


def register_family(*names):
    """Class decorator registering a ServingFamily under one or more
    ``cfg.family`` keys (plus the synthetic ``transformer-dkv`` key the
    engine selects when ``decompose_kv_rank`` is set)."""
    def deco(cls):
        for n in names:
            if n in _REGISTRY:
                raise ValueError(f"serving family {n!r} already registered")
            _REGISTRY[n] = cls
        cls.names = names
        return cls
    return deco


def family_names() -> List[str]:
    return sorted(_REGISTRY)


def serving_family(eng, paged: bool = False) -> "ServingFamily":
    """Resolve the engine's ServingFamily: ``decompose_kv_rank`` selects
    the transformer-dkv path, otherwise the model config's family key."""
    key = "transformer-dkv" if eng.dkv_rank else eng.cfg.family
    cls = _REGISTRY.get(key)
    if cls is None:
        raise ValueError(f"no ServingFamily registered for {key!r} "
                         f"(have {family_names()})")
    return cls(eng, paged=paged)


# ---------------------------------------------------------------------------
# Base protocol = generic dense-cache slab serving
# ---------------------------------------------------------------------------

class ServingFamily:
    """Per-family serving strategy.  The base class IS the generic
    dense-cache slab path: ``init_cache`` slab keyed on each leaf's probed
    batch axis, ``ModelFns``-driven prefill/decode/fused-block builders,
    ``api.splice_cache`` admission, no folds, no pager.  Families override
    only what differs; all mutable arrays live on ``self.eng``.
    """

    #: gang admission may splice into a cache with live slots.  True only
    #: for the plain dense-KV family (the legacy policy's one safe case);
    #: every other family gangs only on an all-free engine.
    gang_live_splice = False
    #: family supports ``Engine(paged=True)``
    paged_capable = False

    def __init__(self, eng, paged: bool = False):
        assert not paged or self.paged_capable, \
            "paged serving runs on the decomposed KV cache (set " \
            "decompose_kv_rank / kv_rank)"
        self.eng = eng
        self._decode, self._prefill = _jitted_steps(
            eng.fns, eng.cfg, eng.max_len, eng.mesh)
        _, self._splice_fam = _jitted_splices(eng.mesh)

    # -- cache lifecycle -------------------------------------------------
    def alloc(self):
        """Build (and mesh-place) the engine's slot cache; None defers
        allocation to the first prefill (shape depends on its result)."""
        eng = self.eng
        return eng._place(eng.fns.init_cache(eng.cfg, eng.slots,
                                             eng.max_len))

    def free_slot(self, slot: int) -> None:
        """Release per-slot resources (paged block tables) on finish."""

    def frozen_after_prefill(self, n: int, plen: int) -> np.ndarray:
        """Per-slot frozen_len right after a prefill of ``plen`` rows."""
        return np.zeros(n, np.int32)

    # -- scheduling ------------------------------------------------------
    def prefill_cost(self, req) -> int:
        """Admission cost the scheduler buckets on.  Token count by
        default; modality families add their fixed extra prefill work."""
        return len(req.prompt)

    def tune_horizon(self) -> int:
        """Decode horizon for the ``decode_block="auto"`` cost model."""
        return self.eng.max_len

    def block_cap(self) -> Optional[int]:
        """Hard upper bound on the fused block length (None = uncapped)."""
        return None

    def fold_horizon(self) -> Optional[int]:
        """Steps until some live slot must fold (None = never folds)."""
        return None

    # -- admission -------------------------------------------------------
    def reserve(self, batch: List[Any], plen: int):
        """Capacity check before dispatch.  Returns an opaque non-None
        context handed to :meth:`dispatch` on success, or None to defer
        the batch (engine requeues it and counts a stall)."""
        return True

    def capacity_msg(self, head) -> str:
        """Diagnostic for a deferral that can never unblock."""
        return (f"request uid={head.uid} (prompt {len(head.prompt)} "
                f"tokens) is blocked on serving capacity with no "
                f"in-flight work to free resources")

    def dispatch(self, batch: List[Any], slots_idx: List[int], plen: int,
                 ctx) -> List[PrefillTicket]:
        """Launch the prefill for one admission batch (batch padded to a
        power of two so compile count stays O(log slots × max_len/bucket))
        and return its tickets.  The prefill is in flight the moment this
        returns; the cache splice and first-token sample happen in
        ``complete()`` (ready-pool splice for async, immediately for
        sync)."""
        eng = self.eng
        with eng.trace.span("admit.prepare"):
            nb = min(_pow2(len(batch)), max(eng.slots, 1))
            toks = eng._toks(batch, nb, plen, lambda j: j)
            args = eng.fns.prefill_inputs(eng.cfg, jnp.asarray(toks),
                                          jnp.zeros)
        with eng.trace.span("admit.launch"):
            logits, fresh = self._prefill(eng.params, *args)
        eng.stats.prefill_batches += 1

        def complete():
            idx = np.asarray(slots_idx, np.int32)
            src = np.arange(len(slots_idx), dtype=np.int32)
            eng.cache = self._splice_fam(eng.cache, fresh, idx, src,
                                         eng.cfg)
            nxt = eng._sample_host(logits, stream=1)[:len(batch)]
            return nxt, np.zeros(len(batch), np.int32)

        return [PrefillTicket(requests=list(batch), slots=list(slots_idx),
                              plen=plen, probe=(logits, fresh),
                              complete=complete, cancel=lambda: None,
                              t_dispatch=time.perf_counter())]

    def gang(self, batch: List[Any], slots_idx: List[int], plen: int,
             has_live: bool) -> Array:
        """Legacy admission: prefill the WHOLE slot batch (idle and live
        slots compute padding), splice rows into a live cache when the
        family supports it, replace the cache wholesale otherwise (all
        slots are free by the gang restriction)."""
        eng = self.eng
        with eng.trace.span("admit.prepare"):
            toks = eng._toks(batch, eng.slots, plen, lambda j: slots_idx[j])
            args = eng.fns.prefill_inputs(eng.cfg, jnp.asarray(toks),
                                          jnp.zeros)
        with eng.trace.span("admit.launch"):
            logits, cache = self._prefill(eng.params, *args)
        if has_live:
            idx = np.asarray(slots_idx, np.int32)
            cache = self._splice_fam(eng.cache, cache, idx, idx, eng.cfg)
        eng.cache = cache
        return logits

    # -- decode ----------------------------------------------------------
    def decode(self, tok: np.ndarray) -> Array:
        """One single-token decode step over every slot; rebinds the
        engine cache and returns the logits (sampling stays host-side in
        ``Engine._sample_host`` — the one sanctioned sync)."""
        eng = self.eng
        logits, eng.cache = self._decode(eng.params, np.asarray(tok),
                                         eng.cache, np.asarray(eng.pos))
        return logits

    def decode_block(self, tok: np.ndarray, n, stops, key, r0):
        """Fused decode: up to ``eng.decode_block`` sampled steps in one
        jitted on-device loop.  Returns ``(token_buf, steps_done)``."""
        eng = self.eng
        fn = _jitted_decode_block(eng.fns, eng.cfg, eng.decode_block,
                                  eng.sampler, eng.mesh)
        buf, steps, _, eng.cache = fn(eng.params, np.asarray(tok),
                                      eng.cache, np.asarray(eng.pos),
                                      n, stops, key, r0)
        return buf, steps

    # -- folds -----------------------------------------------------------
    def maybe_fold(self) -> None:
        """Tail-fold check at a decode/block boundary (no-op unless the
        family compresses a growing cache)."""


# ---------------------------------------------------------------------------
# Concrete families
# ---------------------------------------------------------------------------

@register_family("dense")
class DenseKVServing(ServingFamily):
    """Plain dense-KV transformer serving — the base path unmodified,
    plus the one legacy privilege: gang admission may splice into a live
    cache (row-wise splice-merge has always existed for dense KV)."""
    gang_live_splice = True


@register_family("moe")
class MoEServing(ServingFamily):
    """Mixture-of-experts serving on the dense-KV slab.

    The KV cache is the transformer's (attention is dense); what differs
    is the FFN — ``moe.moe_ffn`` routes top-k per token with a capacity
    buffer.  Routing state is recomputed per step from the hidden states,
    so there is nothing extra to splice: admission, fused blocks, and
    async prefill all ride the base path.  Under a mesh, per-expert
    sharding comes from ``distributed.sharding``'s param rules; the
    engine deliberately leaves ``moe.SHARD_MAP_MESH`` alone so GSPMD
    partitions the expert einsums from the declared shardings (the
    shard_map path is the training/dryrun A/B, not serving).

    Caveat inherited from ``moe_ffn``: expert capacity
    (``ceil(tokens·top_k·cf / num_experts)``) makes token DROPS depend on
    the batch composition — dead-slot padding rows can steal capacity
    from live rows.  Serving conformance therefore pins configs where
    capacity never binds (see tests/test_serving_conformance.py); under
    a binding capacity factor, batched decode is a quality/throughput
    trade, not an exactness bug.
    """


@register_family("ssm")
class Mamba2Serving(ServingFamily):
    """Mamba2/SSM serving: the "cache" is O(1) per slot — conv window
    ``[nl, B, w−1, ch]`` + SSM state ``[nl, B, nh, hd, ds]`` — a STATE
    SLOT with no time axis.  ``pos`` still advances (budget bookkeeping)
    but never indexes device state; folds are no-ops (nothing grows);
    splice admission scatters whole state rows.  Decode cost is constant
    in sequence length, so the fused block is capped only by budget and
    admission horizons."""


@register_family("hybrid")
class HybridServing(ServingFamily):
    """Hybrid (Zamba2-style) serving composes per LAYER: attention
    layers carry sliced KV ``[g, mpg, B, T, kvh, hd]``, mamba layers
    carry state slots — one pytree, mixed leaf kinds.  The generic path
    already handles it: ``api.cache_batch_axes`` probes each leaf's slot
    axis for splicing, and ``distributed.sharding``'s suffix-relative
    leaf rules shard conv/ssm/KV leaves consistently under a mesh."""


@register_family("vlm")
class VLMServing(ServingFamily):
    """Vision-language serving: prefill consumes the image-embedding
    block alongside the tokens (``ModelFns.prefill_inputs``), and every
    admission pays ``num_image_tokens`` of extra attention work — so the
    scheduler buckets on tokens + image tokens, not prompt length."""

    def prefill_cost(self, req) -> int:
        return len(req.prompt) + self.eng.cfg.num_image_tokens


@register_family("audio")
class AudioServing(ServingFamily):
    """Audio encoder-decoder serving: prefill runs the encoder over
    ``num_audio_frames`` frames (the cross-KV cache contract) before the
    decoder touches a token, so admission cost is tokens + frames."""

    def prefill_cost(self, req) -> int:
        return len(req.prompt) + self.eng.cfg.num_audio_frames


@register_family("transformer-dkv")
class TransformerDKVServing(ServingFamily):
    """The paper's low-rank decomposed-KV serving path: prefill
    decomposes K/V through the DecomposeEngine, decode contracts through
    the factors, per-slot dense tails fold back via ``compress_tail``,
    and ``paged=True`` swaps the slab for ``serving.paged``'s page pools
    + prefix cache.  Byte-identical to the pre-protocol engine — every
    method here is the old engine code moved behind the protocol.

    It serves what ``decomposed_kv.unsupported`` passes: the dense family
    and drop-free expert models, whose window layers keep rings beside
    the factorized full layers (slab only: paging holds one kind of
    per-layer state).  For those the gauge ``serving_dkv_layers{kind}``
    counts the factorized and window layers, and the counter
    ``serving_moe_assignments_total{share}`` the token→expert picks of
    admitted prompts whose expert this chip holds (``held``) or another
    (``away``), read back with the first tokens."""
    paged_capable = True

    def __init__(self, eng, paged: bool = False):
        from ..models import decomposed_kv as DK
        why = DK.unsupported(eng.cfg)
        if why is not None:
            raise ValueError(f"decomposed KV: {why}")
        why = DK.paged_unsupported(eng.cfg) if paged else None
        if why is not None:
            raise ValueError(why)
        self.eng = eng
        ec = eng.dengine.config
        self._decode_dkv = _jitted_dkv_decode(eng.cfg, eng.mesh)
        self._prefill_dkv = _jitted_dkv_prefill(
            eng.cfg, ec.backend, ec.expansion, eng.dkv_rank, eng.dkv_tail,
            ec.kv_iters_extra, eng.dkv_exact, eng.mesh)
        self._compress_dkv = _jitted_dkv_compress(eng.cfg, eng.dkv_rank,
                                                  eng.mesh)
        self._splice_dkv, _ = _jitted_splices(eng.mesh)
        reg = eng.obs.registry
        for kind, n in (("factorized", len(DK.factorized_layers(eng.cfg))),
                        ("window", len(DK.window_layers(eng.cfg)))):
            reg.gauge("serving_dkv_layers", "decoder layers by the state "
                      "the decomposed cache keeps", kind=kind).set(n)
        self._picks = {share: reg.counter(
            "serving_moe_assignments_total", "token→expert picks of "
            "admitted prompts, by whether this chip holds the expert",
            share=share) for share in ("held", "away")} \
            if eng.cfg.router_experts else None
        if paged:
            assert eng.admission == "per_slot", "paged serving is per-slot"
            from .paged import PagedDKV
            eng.pager = PagedDKV(
                eng.cfg, slots=eng.slots, max_len=eng.max_len,
                rank=eng.dkv_rank, tail=eng.dkv_tail, page=ec.kv_page,
                pool_pages=ec.kv_pool_pages,
                prefix_capacity=ec.kv_prefix_cache, mesh=eng.mesh)
            if eng.mesh is not None:
                eng.pager.cache = eng._place(eng.pager.cache)

    # -- cache lifecycle -------------------------------------------------
    def alloc(self):
        return None                  # built at first prefill

    def free_slot(self, slot: int) -> None:
        if self.eng.pager is not None:
            self.eng.pager.free_slot(slot)

    def frozen_after_prefill(self, n: int, plen: int) -> np.ndarray:
        return np.full(n, plen, np.int32)

    # -- scheduling ------------------------------------------------------
    def tune_horizon(self) -> int:
        return self.eng.dkv_tail

    def block_cap(self) -> Optional[int]:
        # fold cadence bounds every block — don't trace a longer loop
        return self.eng.dkv_tail

    def fold_horizon(self) -> Optional[int]:
        eng = self.eng
        occ = max(int(eng.pos[i] - eng.frozen_len[i])
                  for i, r in enumerate(eng.live) if r is not None)
        return eng.dkv_tail - occ

    # -- admission -------------------------------------------------------
    def reserve(self, batch: List[Any], plen: int):
        eng = self.eng
        if eng.pager is None:
            return True
        # prefix lookups FIRST (page refs taken per hit), so the
        # reservation below only counts the MISSES' pages and its
        # evictions can never invalidate this batch's hits
        looks = self._lookup_prefixes(batch, plen)
        n_miss = sum(1 for g in looks if g is None)
        if not self._reserve_pages(n_miss, len(batch), plen):
            # page pool can't take this batch yet — release the hit refs
            # taken above (exactly once: they were never installed
            # anywhere) and let the engine requeue + stall
            for got in looks:
                if got is not None:
                    eng.pager.alloc.release(got[2])
            return None
        return looks

    def capacity_msg(self, head) -> str:
        pg = self.eng.pager
        return (f"request uid={head.uid} (prompt {len(head.prompt)} tokens)"
                f" is blocked on page capacity with no in-flight work to "
                f"free pages — raise kv_pool_pages (pool: "
                f"{pg.num_pages} U pages / "
                f"{pg.num_tail_pages} tail pages) or lower the "
                f"prompt length / admission batch")

    def _lookup_prefixes(self, batch: List[Any], plen: int) -> list:
        """Prefix-cache lookups for one admission batch.  Each hit's
        shared page refs are taken IMMEDIATELY — before any reservation
        eviction or same-batch miss insertion can release them — and
        handed to ``_dispatch_paged`` (or dropped on deferral).  Lookups
        run unrecorded (``record=False``): hit/miss stats are counted at
        DISPATCH, exactly once per admitted request, so defer/retry
        cycles can no longer inflate them (each retry used to re-count
        the same request)."""
        eng = self.eng
        pg = eng.pager
        out: list = []
        for req in batch:
            got = None
            if pg.prefix is not None:
                pad = plen - len(req.prompt)
                padded = np.zeros(plen, np.int32)
                padded[pad:] = req.prompt
                found = pg.prefix.lookup(padded, eng.dkv_tail, pad,
                                         record=False)
                if found is not None:
                    ent, match_len = found
                    share = ent.pages[:match_len // pg.page]
                    pg.alloc.ref(share)
                    got = (ent, match_len, share)
            out.append(got)
        return out

    def _reserve_pages(self, n_miss: int, n_req: int, plen: int) -> bool:
        """Can the pools take this batch (``n_miss`` full prefills plus a
        tail per request)?  Evicts prefix-cache entries LRU-first if that
        frees enough — hits are unaffected, they already hold refs."""
        pg = self.eng.pager
        need_u = n_miss * pg.pages_for(plen)
        need_t = n_req * pg.ntp
        while pg.alloc.free_pages < need_u and pg.prefix is not None \
                and len(pg.prefix):
            pg.prefix._evict()
        return pg.alloc.free_pages >= need_u \
            and pg.talloc.free_pages >= need_t

    def dispatch(self, batch: List[Any], slots_idx: List[int], plen: int,
                 ctx) -> List[PrefillTicket]:
        if self.eng.pager is not None:
            looks = ctx if isinstance(ctx, list) else None
            return self._dispatch_paged(batch, slots_idx, plen, looks)
        return [self._dispatch_slab(batch, slots_idx, plen)]

    def _dispatch_slab(self, batch: List[Any], slots_idx: List[int],
                       plen: int) -> PrefillTicket:
        """Launch the slab-path dkv prefill (Lanczos included) for one
        admission batch and return its ticket."""
        eng = self.eng
        with eng.trace.span("admit.prepare"):
            nb = min(_pow2(len(batch)), max(eng.slots, 1))
            toks = eng._toks(batch, nb, plen, lambda j: j)
        with eng.trace.span("admit.launch"):
            logits, fresh, *picks = self._prefill_dkv(eng.params,
                                                      np.asarray(toks))
        eng.stats.prefill_batches += 1

        def complete():
            from ..models import decomposed_kv as DK
            idx = np.asarray(slots_idx, np.int32)
            src = np.arange(len(slots_idx), dtype=np.int32)
            if eng.cache is None:
                eng.cache = eng._place(DK.init_cache(
                    eng.cfg, eng.slots, fresh["k_u"].shape[2],
                    fresh["k_u"].shape[-1], tail=eng.dkv_tail))
            eng.cache = self._splice_dkv(eng.cache, fresh, idx, src)
            eng.rank_eff[slots_idx] = fresh["k_u"].shape[-1]
            if picks:
                nxt, per = eng._sample_host(logits, stream=1, also=picks[0])
                self._count_picks(per)
            else:
                nxt = eng._sample_host(logits, stream=1)
            return nxt[:len(batch)], np.full(len(batch), plen, np.int32)

        return PrefillTicket(requests=list(batch), slots=list(slots_idx),
                             plen=plen, probe=(logits, fresh),
                             complete=complete, cancel=lambda: None,
                             t_dispatch=time.perf_counter())

    def _count_picks(self, per: np.ndarray) -> None:
        """Count one admission's token→expert picks (``per``: picks per
        router expert) as held here or away."""
        cfg = self.eng.cfg
        held = int(per[cfg.expert_first:
                       cfg.expert_first + cfg.num_experts].sum())
        self._picks["held"].inc(held)
        self._picks["away"].inc(int(per.sum()) - held)

    def _dispatch_paged(self, batch: List[Any], slots_idx: List[int],
                        plen: int,
                        looks: Optional[list]) -> List[PrefillTicket]:
        """Paged admission dispatch: the precomputed prefix lookups
        (``looks``, from ``_lookup_prefixes`` — hit page refs already
        taken) split the batch into HITS (tail-only suffix prefill over
        refcounted shared pages — no prefix forward pass, no Lanczos) and
        MISSES (the slot engine's exact prefill path — same jitted fn,
        same pow2 batch padding, so the factors are bit-identical).  One
        ticket per hit group plus one for the misses; all pages are
        allocated and installed in the slot block tables HERE, at
        dispatch, so the reservation holds across the async window and
        ``free_slot`` on cancellation releases everything (shared prefix
        refs exactly once).  Device-side the launch order — suffix chains
        on the pool cache, then the miss scatter — is identical to the
        pre-split engine; only the host-side sample/bookkeeping moves
        into ``complete()``."""
        eng = self.eng
        pg = eng.pager
        n = len(batch)
        padded = eng._padded(batch, n, plen, lambda j: j)
        hits: dict = {}            # (L, r_eff) -> [(j, entry, share), ...]
        misses: List[int] = []
        for j in range(n):
            got = looks[j] if looks is not None else None
            if got is not None:
                ent, match_len, share = got
                hits.setdefault((match_len, ent.r_eff),
                                []).append((j, ent, share))
            else:
                misses.append(j)
        if pg.prefix is not None:
            # counted once per ADMITTED request, here at dispatch — the
            # lookups themselves ran record=False, so a defer/retry cycle
            # no longer double-counts (engine stats and cache counters)
            nh = n - len(misses)
            eng.stats.prefix_hits += nh
            eng.stats.prefix_misses += len(misses)
            pg.prefix.hits += nh
            pg.prefix.misses += len(misses)

        tickets: List[PrefillTicket] = []
        # hits first: they only consume tail pages, and their factor
        # pages already carry this batch's refs
        for (match_len, r_ent), group in sorted(hits.items()):
            tickets.append(self._dispatch_paged_hits(
                batch, slots_idx, plen, padded, match_len, r_ent, group))
        if misses:
            tickets.append(self._dispatch_paged_miss(
                batch, slots_idx, plen, padded, misses))
        return tickets

    def _dispatch_paged_hits(self, batch: List[Any],
                             slots_idx: List[int], plen: int,
                             padded: np.ndarray, match_len: int,
                             r_ent: int, group: list) -> PrefillTicket:
        eng = self.eng
        pg = eng.pager
        with eng.trace.span("admit.prepare"):
            m = len(group)
            stoks = np.zeros((m, plen - match_len), np.int32)
            ent_bt, bt_t, idx = [], [], []
            reqs: List[Any] = []
            slots_l: List[int] = []
            shares: List[list] = []
            for gi, (j, ent, share) in enumerate(group):
                slot = slots_idx[j]
                stoks[gi] = padded[j][match_len:]
                tpages = pg.talloc.alloc(pg.ntp)
                assert tpages is not None, "tail pages after _reserve_pages"
                ent_bt.append(share)
                shares.append(list(share))
                bt_t.append(tpages)
                idx.append(slot)
                reqs.append(batch[j])
                slots_l.append(slot)
            # the suffix holds a prompt's last min(len, plen − match_len)
            # tokens; the rest of its row is left padding
            eng._count_prefill(stoks, sum(
                min(len(r.prompt), plen - match_len) for r in reqs))
            k_vt = jnp.stack([ent.k_vt for _, ent, _ in group], axis=1)
            v_vt = jnp.stack([ent.v_vt for _, ent, _ in group], axis=1)
            start = np.full(m, match_len, np.int32)
            slen = np.full(m, plen - match_len, np.int32)
        with eng.trace.span("admit.launch"):
            logits, pg.cache = pg._suffix(
                eng.params, np.asarray(stoks), pg.cache,
                np.asarray(ent_bt, np.int32), k_vt, v_vt,
                np.asarray(start), np.asarray(slen),
                np.asarray(bt_t, np.int32), np.asarray(idx, np.int32),
                match_len, r_ent)
        eng.stats.prefill_batches += 1

        def complete():
            # install the block tables only NOW: while the ticket was in
            # flight the slot's bt rows stayed empty (SINK-padded in
            # bt_array), so intervening decode launches scattered their
            # dead-row writes into the sink page instead of the suffix
            # tail pages written at dispatch.  The shared-prefix ref from
            # _lookup_prefixes transfers to the slot here; free_slot
            # releases it exactly once.
            for gi, slot in enumerate(slots_l):
                pg.bt_u[slot], pg.bt_t[slot] = shares[gi], bt_t[gi]
                eng.rank_eff[slot] = r_ent
            nxt = eng._sample_host(logits, stream=1)[:m]
            pg.slab_t = max(pg.slab_t, match_len)
            pg.slab_r = max(pg.slab_r, r_ent)
            return nxt, np.full(m, match_len, np.int32)

        def cancel():
            # nothing was installed in the slot block tables yet, so the
            # lookup's shared ref and the fresh tail pages are released
            # directly (exactly once each)
            for gi in range(m):
                pg.alloc.release(shares[gi])
                pg.talloc.release(bt_t[gi])

        return PrefillTicket(requests=reqs, slots=slots_l, plen=plen,
                             probe=logits, complete=complete,
                             cancel=cancel,
                             t_dispatch=time.perf_counter())

    def _dispatch_paged_miss(self, batch: List[Any],
                             slots_idx: List[int], plen: int,
                             padded: np.ndarray,
                             misses: List[int]) -> PrefillTicket:
        eng = self.eng
        pg = eng.pager
        with eng.trace.span("admit.prepare"):
            nb = min(_pow2(len(misses)), max(eng.slots, 1))
            mtoks = np.zeros((nb, plen), np.int32)
            for mi, j in enumerate(misses):
                mtoks[mi] = padded[j]
            eng._count_prefill(mtoks,
                               sum(len(batch[j].prompt) for j in misses))
        with eng.trace.span("admit.launch"):
            logits, fresh = self._prefill_dkv(eng.params, np.asarray(mtoks))
        eng.stats.prefill_batches += 1
        npg = pg.pages_for(plen)
        bt_u, bt_t, idx = [], [], []
        reqs: List[Any] = []
        slots_l: List[int] = []
        for j in misses:
            slot = slots_idx[j]
            pages = pg.alloc.alloc(npg)
            tpages = pg.talloc.alloc(pg.ntp)
            assert pages is not None and tpages is not None, \
                "page reservation failed after _reserve_pages"
            bt_u.append(pages)
            bt_t.append(tpages)
            idx.append(slot)
            reqs.append(batch[j])
            slots_l.append(slot)
        pads = [plen - len(batch[j].prompt) for j in misses]
        rows = [padded[j].copy() for j in misses]

        def complete():
            # block tables are installed only now (see the hit-path note:
            # bt rows stay SINK during the async window so dead-row decode
            # writes can't touch the reserved pages); the _admit scatter
            # below chains device-side AFTER any intervening decode, so it
            # owns the final contents of every factor/tail page
            r_eff = fresh["k_u"].shape[-1]
            src = np.arange(len(misses), dtype=np.int32)
            pg.cache = pg._admit(pg.cache, fresh["k_u"], fresh["v_u"],
                                 fresh["k_vt"], fresh["v_vt"],
                                 np.asarray(bt_u, np.int32),
                                 np.asarray(bt_t, np.int32),
                                 np.asarray(idx, np.int32), src)
            for mi, slot in enumerate(slots_l):
                pg.bt_u[slot], pg.bt_t[slot] = bt_u[mi], bt_t[mi]
                eng.rank_eff[slot] = r_eff
            nxt = eng._sample_host(logits, stream=1)[:len(misses)]
            pg.slab_t = max(pg.slab_t, plen)
            pg.slab_r = max(pg.slab_r, r_eff)
            if pg.prefix is not None:
                for mi, slot in enumerate(slots_l):
                    pg.prefix.insert(rows[mi], pg.bt_u[slot],
                                     fresh["k_vt"][:, mi],
                                     fresh["v_vt"][:, mi], r_eff,
                                     n_pad=pads[mi])
            return nxt, np.full(len(misses), plen, np.int32)

        def cancel():
            for mi in range(len(misses)):
                pg.alloc.release(bt_u[mi])
                pg.talloc.release(bt_t[mi])

        return PrefillTicket(requests=reqs, slots=slots_l, plen=plen,
                             probe=(logits, fresh), complete=complete,
                             cancel=cancel,
                             t_dispatch=time.perf_counter())

    def gang(self, batch: List[Any], slots_idx: List[int], plen: int,
             has_live: bool) -> Array:
        eng = self.eng
        with eng.trace.span("admit.prepare"):
            toks = eng._toks(batch, eng.slots, plen, lambda j: slots_idx[j])
        with eng.trace.span("admit.launch"):
            logits, eng.cache, *_ = self._prefill_dkv(eng.params,
                                                      np.asarray(toks))
        eng.rank_eff[slots_idx] = eng.cache["k_u"].shape[-1]
        return logits

    # -- decode ----------------------------------------------------------
    def decode(self, tok: np.ndarray) -> Array:
        eng = self.eng
        if eng.pager is not None:
            pg = eng.pager
            logits, pg.cache = pg._decode(
                eng.params, np.asarray(tok), pg.cache,
                np.asarray(eng.pos),
                np.asarray(eng.frozen_len),
                np.asarray(pg.bt_array(pg.bt_u)),
                np.asarray(pg.bt_array(pg.bt_t, pg.ntp)),
                pg.slab_t, pg.slab_r, eng.dkv_tail)
            return logits
        logits, eng.cache = self._decode_dkv(
            eng.params, np.asarray(tok), eng.cache,
            np.asarray(eng.pos),
            np.asarray(eng.frozen_len))
        return logits

    def decode_block(self, tok: np.ndarray, n, stops, key, r0):
        eng = self.eng
        if eng.pager is not None:
            pg = eng.pager
            from .paged import _jitted_paged_decode_block
            fn = _jitted_paged_decode_block(eng.cfg, eng.decode_block,
                                            eng.sampler, eng.mesh)
            buf, steps, _, pg.cache = fn(
                eng.params, np.asarray(tok), pg.cache,
                np.asarray(eng.pos), np.asarray(eng.frozen_len),
                np.asarray(pg.bt_array(pg.bt_u)),
                np.asarray(pg.bt_array(pg.bt_t, pg.ntp)),
                n, stops, key, r0, pg.slab_t, pg.slab_r, eng.dkv_tail)
            return buf, steps
        fn = _jitted_dkv_decode_block(eng.cfg, eng.decode_block,
                                      eng.sampler, eng.mesh)
        buf, steps, _, eng.cache = fn(
            eng.params, np.asarray(tok), eng.cache,
            np.asarray(eng.pos), np.asarray(eng.frozen_len),
            n, stops, key, r0)
        return buf, steps

    # -- folds -----------------------------------------------------------
    def maybe_fold(self) -> None:
        """Tail-fold check at a decode/block boundary (decomposed KV)."""
        eng = self.eng
        live_m = np.array([r is not None for r in eng.live])
        occ = eng.pos - eng.frozen_len
        must = live_m & (occ >= eng.dkv_tail)
        if must.any():
            # a slot's tail is full — fold it, and opportunistically
            # co-fold every live slot at least half full: co-folded
            # slots restart at occupancy 0 together, re-synchronizing
            # fold cadence under staggered admissions (fold ≈ one
            # event per TAIL decode rounds instead of one per slot).
            # A co-folded slot's unused tail rows are zeros and fold
            # as zero rows — exactness is unaffected.
            fold = must | (live_m & (occ >= max(1, eng.dkv_tail // 2)))
            with eng.trace.span("fold", "engine",
                                {"slots": int(fold.sum())}), \
                    phase_scope("fold"):
                if eng.pager is not None:
                    self._fold_slots_paged(live_m, must, fold)
                else:
                    self._fold_slots(live_m, fold)

    def _fold_slots(self, live_m: np.ndarray, fold: np.ndarray) -> None:
        """Per-slot tail fold on the SLAB cache (non-paged path)."""
        from ..models import decomposed_kv as DK
        eng = self.eng
        r_in = int(eng.cache["k_u"].shape[-1])
        t_frozen = int(eng.cache["k_u"].shape[2])
        new_frozen = np.where(fold, eng.pos,
                              eng.frozen_len).astype(np.int32)
        eng.cache = self._compress_dkv(eng.cache,
                                       np.asarray(eng.frozen_len),
                                       np.asarray(fold),
                                       np.asarray(new_frozen))
        eng.frozen_len = new_frozen
        eng.rank_eff = np.where(
            fold, DK.fold_rank(eng.dkv_rank, r_in, t_frozen,
                               eng.dkv_tail),
            eng.rank_eff).astype(np.int32)
        eng.stats.tail_folds += int(fold.sum())
        # keep only the rows AND factor columns live slots reference — a
        # finished slot's stale frozen_len/rank must not pin memory, and
        # the rank axis shrinks back to the configured kv_rank once
        # wide-rank splices drain (the old behavior ratcheted forever)
        t_need = int(eng.frozen_len[live_m].max())
        r_need = int(eng.rank_eff[live_m].max())
        for key in ("k_u", "v_u"):
            eng.cache[key] = eng.cache[key][:, :, :t_need, :r_need]
        for key in ("k_vt", "v_vt"):
            eng.cache[key] = eng.cache[key][:, :, :r_need]

    def _fold_slots_paged(self, live_m: np.ndarray, must: np.ndarray,
                          fold: np.ndarray) -> np.ndarray:
        """Paged tail fold: retruncated prefixes land in FRESH pages
        (copy-on-write — shared/prefix-cache pages are never rewritten);
        the folded slots' old page refs are released after the scatter.
        Falls back to must-only folds when the pool can't take the
        opportunistic co-folds."""
        from ..models import decomposed_kv as DK
        eng = self.eng
        pg = eng.pager

        def grab(mask):
            idxs = [int(i) for i in np.where(mask)[0]]
            need = {i: pg.pages_for(int(eng.pos[i])) for i in idxs}
            if sum(need.values()) > pg.alloc.free_pages:
                return None
            return {i: pg.alloc.alloc(n) for i, n in need.items()}

        newp = grab(fold)
        if newp is None:
            fold = must
            newp = grab(fold)
        while newp is None and pg.prefix is not None and len(pg.prefix):
            pg.prefix._evict()
            newp = grab(fold)
        if newp is None:
            raise RuntimeError(
                "paged KV pool exhausted during a tail fold — raise "
                "kv_pool_pages (or lower slots/max_len)")
        npn = max(len(v) for v in newp.values())
        bt_new = pg.bt_array([newp.get(i, []) for i in range(eng.slots)],
                             npn)
        new_frozen = np.where(fold, eng.pos,
                              eng.frozen_len).astype(np.int32)
        pg.cache = pg._fold(
            pg.cache, np.asarray(eng.frozen_len), np.asarray(fold),
            np.asarray(new_frozen), np.asarray(pg.bt_array(pg.bt_u)),
            np.asarray(bt_new), np.asarray(pg.bt_array(pg.bt_t, pg.ntp)),
            pg.slab_t, pg.slab_r, eng.dkv_tail)
        r_fold = DK.fold_rank(eng.dkv_rank, pg.slab_r, pg.slab_t,
                              eng.dkv_tail)
        for i, pages in newp.items():
            pg.alloc.release(pg.bt_u[i])
            pg.bt_u[i] = pages
            eng.rank_eff[i] = r_fold
        eng.frozen_len = new_frozen
        eng.stats.tail_folds += int(fold.sum())
        pg.slab_t = int(eng.frozen_len[live_m].max())
        pg.slab_r = int(eng.rank_eff[live_m].max())
        return fold
