"""Serving engine: batched prefill/decode with continuous batching.

A slot-based engine (vLLM-style, sized for the dry-run meshes): ``slots``
concurrent sequences share one static cache; finished sequences free their
slot; queued requests prefill into free slots.

The engine is FAMILY-GENERIC: everything model-family-specific — cache
allocation, splice admission, prefill/decode/fused-block builders, tail
folds, paged-layout adapters, scheduler admission cost — lives behind the
:class:`~repro.serving.families.ServingFamily` protocol, resolved once at
construction (``serving.families.serving_family``).  One engine serves
transformer (dense or decomposed-KV), Mamba2/SSM state slots, MoE,
hybrid, VLM, and audio encoder-decoder traffic; this module contains no
per-family branches (dcomlint rule F1 gates regressions), only the
family-agnostic machinery: slots, scheduler, tickets, stats, and the
step loop.

Admission is PER SLOT (``admission="per_slot"``, the default): only the
newly admitted requests are prefilled — batch and length rounded up to
scheduler buckets to bound re-jits — and the fresh cache rows are spliced
into the live cache along each leaf's batch axis (``api.splice_cache``,
every family; ``decomposed_kv.splice_dkv`` for the low-rank KV cache).
Live slots are never re-prefilled and admission never waits for them to
drain.  ``admission="gang"`` keeps the legacy policy (whole-slot-batch
prefill; decomposed-KV and non-dense families block until every slot is
free) for A/B comparison in ``benchmarks/serving_admission.py``.

``decompose_kv_rank`` serves the dense family on the paper's low-rank KV
cache (models.decomposed_kv): prefill decomposes K/V, decode contracts
through the factors, and each slot's dense tail is folded back
(``compress_tail`` with a per-slot fold mask) when THAT slot's tail
fills — plus opportunistic co-folding of half-full neighbors to
re-synchronize fold cadence.  ``frozen_len`` is a per-slot vector, not a
global scalar.

The :class:`Scheduler` dispatches FIFO with prefill-length bucketing (one
plen bucket per admission LAUNCH; ``_admit`` drains further buckets into
the remaining free slots, so mixed-length queues no longer idle slots
behind the head bucket); bucketing runs on the family's ADMISSION COST
(prompt tokens plus fixed modality work — image tokens, encoder frames),
not raw prompt length.  ``EngineStats`` tracks per-request first-token
and inter-token latency, and wall time accrues per ``step()``.  Requests
stop the moment they emit ``eos_id`` (or any of ``stop_tokens``) — the
slot frees immediately — with stopped-vs-budget finishes counted
separately.

``paged=True`` (decomposed-KV only) swaps the ``[slots, max_len, …]``
slab for the paged layout of ``serving.paged``: prefix U rows and dense
tail rows live in fixed-size page pools behind per-slot block tables, a
refcounted :class:`~repro.serving.paged.PageAllocator` recycles pages
across requests, and an optional hash-based prefix cache
(``EngineConfig.kv_prefix_cache``) admits a request whose padded prompt
extends a cached frozen prefix with TAIL-ONLY work — shared pages are
spliced by refcount, skipping both the prefix forward pass and its
Lanczos factorization.  With the prefix cache off, paged decode/fold
replays the slab engine's arithmetic bit-for-bit
(tests/test_serving_conformance.py).

Mesh-parallel serving: when the DecomposeEngine's config carries a
``mesh``, every cache (dense k/v AND the low-rank ``k_u``/``k_vt``
factors — and the SSM/hybrid state slots) is allocated on
``distributed.sharding.cache_sharding`` — slots over the DP super-axis,
KV heads / kv width over "model" — and every jitted step fn constrains
its cache inputs/outputs to the same specs, so splice admission,
per-slot ``frozen_len`` masking, and ``compress_tail`` folds all stay
device-local along the batch axis (no gather-to-host; the tail write is
a vmapped per-slot ``dynamic_update_slice``).  Greedy outputs are
byte-identical to the single-device engine
(tests/test_serving_conformance.py runs the 8-host-device twin).

``decode_block > 1`` fuses that many decode rounds into ONE jitted
on-device loop (``api.run_decode_block``): sampling runs on device, the
per-step host dispatch + sampler round-trip + python stop check are paid
once per BLOCK, and the host applies EOS/stop/budget bookkeeping in one
pass over the returned token buffer.  Tokens stay byte-identical to the
single-step engine by construction: the host computes every upcoming
boundary event deterministically (steps until the next tail fold from
``pos``/``frozen_len``/``dkv_tail``, the tightest budget horizon, the
next admission round) and caps the block there, and the loop exits early
the moment any slot emits a stop token — so folds, admissions, and
finishes all happen between blocks at exactly the rounds the single-step
engine would have run them (DESIGN.md §11).

``prefill_async=True`` disaggregates prefill from decode (vLLM-style
P/D split, DESIGN.md §12): ``_admit`` only DISPATCHES the prefill —
forward + Lanczos for misses, tail-only suffix prefill for prefix-cache
hits — as a :class:`~repro.serving.families.PrefillTicket` into the
engine's prefill pool, with the target slots reserved and (paged mode)
the pages/refs already held, then returns to the decode loop.  JAX
dispatch is asynchronous, so the Lanczos factorization runs device-side
while live slots keep decoding; the ticket's results are spliced into
the reserved slots at a later step boundary once ``api.tree_ready`` (a
non-blocking ``Array.is_ready`` probe over the result tree) reports them
done — decode never blocks on an in-flight decomposition.
``ready_order="ready"`` splices tickets as they complete (dispatch order
among the simultaneously-ready); ``ready_order="deterministic"``
completes every ticket inline at its dispatch round — the synchronous
engine's schedule driven through the identical dispatch/complete
machinery, which is the conformance mode: tokens are byte-identical to
``prefill_async=False`` (tests/test_serving_async.py, slot AND paged,
single AND fused decode, 1 and 8 devices).  ``cancel_pending`` unwinds
in-flight tickets: reserved slots free, page refs release, requests
requeue in arrival order.

All jitted decode/fold/splice fns DONATE their cache arguments
(``donate_argnums``): the engine rebinds ``self.cache`` (or the paged
pools) immediately at every call site, so XLA reuses the input buffers
in place instead of holding both generations live.  Shape-growing calls
(a fold extending the time axis, a widening splice) can't alias every
leaf — jax warns "Some donated buffers were not usable" there, which is
expected and filtered.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

# Expected consequence of best-effort donation: shape-growing folds and
# splices cannot reuse every donated leaf (see module docstring).
warnings.filterwarnings("ignore",
                        message="Some donated buffers were not usable")

from ..configs.base import ArchConfig
from ..engine import DecomposeEngine, EngineConfig
from ..models import api
from ..obs import (NULL_SPAN, LatencySeries, MetricsRegistry, Observability,
                   phase_scope)
from .families import (PrefillTicket, ServingFamily,  # noqa: F401
                       family_names, register_family, serving_family)

Array = jax.Array

#: the engine span of a host sampling readback, by sample stream
_READBACK_SPAN = {0: "decode.readback", 1: "admit.first_token"}


def greedy_sampler(logits: Array, k: int) -> Array:
    """Default sampler: argmax over the vocab axis.  Module-level (not a
    per-engine lambda) so the fused decode-block executables, which are
    lru-keyed on the sampler, are shared across engines."""
    return jnp.argmax(logits, -1).astype(jnp.int32)


def categorical_sampler(temperature: float = 1.0) -> Callable:
    """Stochastic sampler for the on-device fused loop.  ``takes_key``
    marks it as keyed: both decode paths derive the per-round key as
    ``fold_in(stream_key, round_index)``, so any interleaving of block
    sizes samples the identical token sequence."""
    def sample(logits: Array, k: int, key) -> Array:
        lg = logits.astype(jnp.float32) / max(temperature, 1e-6)
        return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)
    sample.takes_key = True
    return sample


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray               # [S] int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None     # stop token (None = engine default)
    stop_tokens: Tuple[int, ...] = ()   # extra stop tokens
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    seq: int = -1                    # scheduler arrival stamp (FIFO key —
    #                                  deferral requeues merge on it)
    # -- latency accounting (monotonic perf_counter stamps, 0.0 = not yet)
    t_submit: float = 0.0
    t_dispatch: float = 0.0          # prefill launched (queue wait ends)
    t_first: float = 0.0             # first token emitted (prefill sample)
    t_last: float = 0.0              # most recent token
    t_done: float = 0.0


class EngineStats:
    """Per-engine serving counters + latency distributions, mounted on a
    ``repro.obs`` :class:`MetricsRegistry` (DESIGN.md §13).

    The attribute API is unchanged from the pre-obs dataclass — counters
    read/write as plain numbers (``stats.prefills += 1``), and the
    latency members (``ttft_s``/``ttft_queue_s``/``ttft_compute_s``/
    ``itl_s``) still ``append``/``extend``/iterate like lists — but the
    storage moved onto registry metrics: counters are ``Counter``s,
    latencies are O(1)-memory streaming histograms with a CAPPED
    recent-sample reservoir instead of the old unbounded per-request
    Python lists.  ``len(itl_s)`` reports the total observation count
    (the histogram counter), so the ``len(itl_s) == tokens_out``
    invariant survives the bound; iteration yields only the recent
    window.  ``mean_*`` come from the exact streaming sum/count, and
    p50/p95/p99 are available via ``.quantile(q)`` on any series.
    """

    _COUNTERS = (
        ("prefills", "admitted requests (one per request)"),
        ("prefill_batches", "admission batches (jit launches)"),
        ("decode_steps", "decode rounds (one token per live slot)"),
        ("blocks", "decode launches (== steps unless the fused loop "
                   "batches rounds per dispatch)"),
        ("tokens_out", "decode tokens emitted"),
        ("tail_folds", "per-slot compress_tail events"),
        ("stopped_eos", "requests finished on a stop token"),
        ("stopped_budget", "requests finished on max_new_tokens/max_len"),
        ("prefix_hits", "admissions served from the prefix cache"),
        ("prefix_misses", "prefix lookups that fell through to prefill"),
        ("stalls", "admissions deferred on page capacity"),
        ("wall_s", "wall seconds accrued per step()"),
    )
    _GAUGES = (
        ("prefill_inflight_peak",
         "max concurrently in-flight prefill tickets (async mode)"),
    )
    _HISTS = (
        ("ttft_s", "ttft_seconds", "submit to first token"),
        # TTFT split (aligned 1:1 with ttft_s): queue wait (submit →
        # prefill dispatch) vs prefill compute (dispatch → first token).
        # The async A/B compares queue wait — compute is the same device
        # work either way.
        ("ttft_queue_s", "ttft_queue_seconds",
         "queue wait: submit to prefill dispatch"),
        ("ttft_compute_s", "ttft_compute_seconds",
         "prefill compute: dispatch to first token"),
        ("itl_s", "itl_seconds", "inter-token latency"),
    )

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self._m = {}
        for name, help_ in self._COUNTERS:
            metric = "serving_wall_seconds" if name == "wall_s" \
                else f"serving_{name}"
            self._m[name] = self.registry.counter(metric, help_)
        for name, help_ in self._GAUGES:
            self._m[name] = self.registry.gauge(f"serving_{name}", help_)
        for name, metric, help_ in self._HISTS:
            self._m[name] = LatencySeries(
                self.registry.histogram(f"serving_{metric}", help_))
        # entries of the prefill token matrices launched: real prompt
        # tokens, and bucket and batch padding (``Engine._count_prefill``)
        self.prefill_tokens = {
            kind: self.registry.counter(
                "serving_prefill_tokens_total",
                "prefill token-matrix entries launched", kind=kind)
            for kind in ("prompt", "pad")}

    def __repr__(self) -> str:
        return (f"EngineStats(prefills={self.prefills}, "
                f"tokens_out={self.tokens_out}, "
                f"decode_steps={self.decode_steps})")

    @property
    def mean_ttft_s(self) -> float:
        return self.ttft_s.mean

    @property
    def mean_ttft_queue_s(self) -> float:
        return self.ttft_queue_s.mean

    @property
    def mean_ttft_compute_s(self) -> float:
        return self.ttft_compute_s.mean

    @property
    def mean_itl_s(self) -> float:
        return self.itl_s.mean

    def snapshot(self, wall_s: Optional[float] = None) -> dict:
        """The uniform ``repro.obs/v1`` metrics snapshot (benchmarks and
        the serve CLI embed this; see ``obs.snapshot``)."""
        from ..obs import stats_snapshot
        return stats_snapshot(self, wall_s=wall_s)


def _stat_counter(name: str) -> property:
    return property(lambda self: self._m[name].value,
                    lambda self, v: self._m[name].set(v))


for _name, _ in EngineStats._COUNTERS + EngineStats._GAUGES:
    setattr(EngineStats, _name, _stat_counter(_name))
for _name, _metric, _ in EngineStats._HISTS:
    setattr(EngineStats, _name,
            property(lambda self, _n=_name: self._m[_n]))
del _name, _metric


class Scheduler:
    """FIFO request queue with prefill-length bucketing.

    ``next_batch`` serves the HEAD of the queue plus any later requests
    falling in the same prefill-cost bucket (FIFO order within the
    bucket), so one admission batch compiles exactly one (batch, plen)
    shape.  Bucketing runs on ``cost(request)`` — the family's reported
    admission cost (prompt tokens by default; modality families add
    their fixed extra prefill work, e.g. image tokens or encoder
    frames), rounded up to multiples of ``bucket``; admitted batch size
    is capped at ``max_admit`` (0 = number of free slots).

    Every submission is stamped with a monotonically increasing arrival
    ``seq``; :meth:`requeue` merges a deferred batch back on that stamp,
    so a deferral can never leapfrog requests that arrived between the
    batch's members (the old front-insertion reordered cross-bucket:
    taking [a, c] out of [a(16), b(32), c(16)] and pushing the batch back
    to the front yielded [a, c, b] — c jumped b's place in line).
    """

    def __init__(self, bucket: int = 16, max_admit: int = 0,
                 cost: Optional[Callable[[Request], int]] = None):
        self.bucket = max(1, bucket)
        self.max_admit = max_admit
        self.cost = cost if cost is not None \
            else (lambda r: len(r.prompt))
        self._q: List[Request] = []
        self._seq = 0

    def submit(self, req: Request) -> None:
        if req.seq < 0:
            req.seq = self._seq
            self._seq += 1
        self._q.append(req)

    def requeue(self, batch: List[Request]) -> None:
        """Return a deferred (or cancelled) batch to the queue in ARRIVAL
        order — a stable merge on the submission stamp, not a front
        insertion."""
        self._q = sorted(self._q + list(batch), key=lambda r: r.seq)

    def __len__(self) -> int:
        return len(self._q)

    @property
    def pending(self) -> List[Request]:
        return list(self._q)

    def bucket_of(self, plen: int) -> int:
        return -(-max(int(plen), 1) // self.bucket) * self.bucket

    def next_batch(self, free_slots: int) -> List[Request]:
        if not self._q or free_slots < 1:
            return []
        cap = free_slots if self.max_admit < 1 \
            else min(free_slots, self.max_admit)
        want = self.bucket_of(self.cost(self._q[0]))
        take: List[Request] = []
        keep: List[Request] = []
        # Ride-along fairness: a later same-bucket request may join the
        # head's batch only while a slot remains for every OLDER skipped
        # bucket — each will want its own launch this admission round.
        # Without the reservation, a young ride-along could take the last
        # free slot from an older other-bucket request and push its first
        # token a full admission round out (head-bucket starvation).
        skipped = set()
        for r in self._q:
            bk = self.bucket_of(self.cost(r))
            if bk == want and len(take) + len(skipped) < cap:
                take.append(r)
            else:
                keep.append(r)
                if bk != want:
                    skipped.add(bk)
        self._q = keep
        return take


class Engine:
    """Continuous-batching engine over the unified model API.

    Decode advances every live slot one token per step; admission splices
    only the newly prefilled rows into the live cache (per-slot policy).
    Every family-specific operation dispatches through ``self.family``
    (a :class:`~repro.serving.families.ServingFamily`).
    """

    def __init__(self, cfg: ArchConfig, params, *, slots: int = 4,
                 max_len: int = 256, sampler: Optional[Callable] = None,
                 decompose_kv_rank: Optional[int] = None,
                 dkv_tail: Optional[int] = None,
                 decompose_engine: Optional[DecomposeEngine] = None,
                 admission: str = "per_slot",
                 dkv_exact: Optional[bool] = None,
                 eos_id: Optional[int] = None,
                 paged: bool = False,
                 decode_block: Optional[Union[int, str]] = None,
                 prefill_async: Optional[bool] = None,
                 ready_order: str = "ready",
                 sample_seed: int = 0,
                 obs: Optional[Observability] = None):
        assert admission in ("per_slot", "gang"), admission
        assert ready_order in ("ready", "deterministic"), ready_order
        # Observability bundle (DESIGN.md §13): per-engine metrics
        # registry + tracer.  Purely host-side — spans and counters never
        # feed a jit or touch device state, so tokens are byte-identical
        # with tracing on or off (conformance-gated).
        self.obs = obs if obs is not None else Observability()
        self.trace = self.obs.tracer
        self.cfg, self.params = cfg, params
        self.slots, self.max_len = slots, max_len
        self.admission = admission
        self.eos_id = eos_id             # default stop token for requests
        self.fns = api.model_fns(cfg)
        self.sampler = sampler or greedy_sampler
        # base PRNG key for keyed samplers (categorical_sampler): decode
        # rounds fold stream 0, admission rounds stream 1 — both indexed
        # by the engine's round counter, so the single-step and fused
        # paths draw identical samples
        self._key = jax.random.PRNGKey(sample_seed)
        # One DecomposeEngine per serving engine: backend/hook selection
        # happens here, once, and every prefill decomposition reuses it.
        # An explicitly passed knob always wins (0 DISABLES decomposed KV);
        # None knobs inherit from the engine config when one is supplied.
        if decompose_engine is not None:
            self.dengine = decompose_engine
            if decompose_kv_rank is None:
                decompose_kv_rank = decompose_engine.config.kv_rank
            if dkv_tail is None:
                dkv_tail = decompose_engine.config.kv_tail
        else:
            decompose_kv_rank = decompose_kv_rank or 0
            if dkv_tail is None:
                dkv_tail = 16
            self.dengine = DecomposeEngine(EngineConfig(
                kv_rank=decompose_kv_rank, kv_tail=dkv_tail))
        self.dkv_rank = decompose_kv_rank
        self.dkv_tail = dkv_tail
        self.dkv_exact = self.dengine.config.kv_exact \
            if dkv_exact is None else dkv_exact
        # Mesh-parallel serving: the engine config's mesh shards every
        # cache along the batch (slot) axis over the DP super-axis (and KV
        # heads / kv width over "model") per distributed.sharding's spec
        # tables; None keeps the single-device path bit-identical.
        self.mesh = self.dengine.config.mesh
        # per-slot state: pos is the next write position, frozen_len the
        # length of the slot's low-rank prefix, rank_eff its effective
        # factor rank (dkv path only — lets the engine slice the rank
        # axis back down when wide-rank occupants leave or fold)
        self.pos = np.zeros((slots,), np.int32)
        self.frozen_len = np.zeros((slots,), np.int32)
        self.rank_eff = np.zeros((slots,), np.int32)
        self.live: List[Optional[Request]] = [None] * slots
        # the per-family strategy: cache layout, splice admission, jitted
        # step builders, folds, and (transformer-dkv) the paged adapter —
        # resolving it also constructs self.pager when paged
        self.pager = None
        self.family = serving_family(self, paged=paged)
        self.cache = self.family.alloc()
        ecfg = self.dengine.config
        self.sched = Scheduler(bucket=ecfg.sched_bucket,
                               max_admit=ecfg.sched_max_admit,
                               cost=self.family.prefill_cost)
        self.admit_every = max(1, ecfg.sched_admit_every)
        # fused decode-block length: explicit arg wins, else the engine
        # config; "auto" resolves through the repro.tune cost model for
        # this (slots, decode horizon, kv width) bucket.  1 = the
        # single-step path, bit-identical to the pre-fusion engine.
        blk = ecfg.decode_block if decode_block is None else decode_block
        if blk == "auto":
            from .. import tune
            horizon = self.family.tune_horizon()
            kvw = cfg.num_kv_heads * cfg.resolved_head_dim
            blk = tune.tuned_decode_block((slots, horizon, kvw))
        self.decode_block = max(1, int(blk))
        cap = self.family.block_cap()
        if cap is not None:
            self.decode_block = min(self.decode_block, cap)
        # -- async prefill/decode disaggregation (DESIGN.md §12) --------
        # prefill_async: explicit arg wins, else the engine config.
        # ready_order="ready" splices tickets as their device results
        # come ready (the true async mode — decode never blocks on an
        # in-flight Lanczos); "deterministic" completes each ticket at
        # its dispatch round, replaying the synchronous schedule through
        # the identical ticket machinery (the byte-identity conformance
        # mode).  Sync admission and deterministic mode share one code
        # path; only "ready" populates the pool across steps.
        if prefill_async is None:
            prefill_async = ecfg.prefill_async
        self.prefill_async = bool(prefill_async)
        self.ready_order = ready_order
        assert not (self.prefill_async and admission == "gang"), \
            "async prefill requires per-slot admission (gang replaces " \
            "the whole cache — there is nothing to overlap)"
        self._pool: List[PrefillTicket] = []     # in-flight admissions
        self._reserved = np.zeros(slots, bool)   # dispatched, not spliced
        self.admit_log: List[int] = []           # uids in dispatch order
        self.stats = EngineStats(registry=self.obs.registry)
        # open request-lifecycle spans: uid -> {"request"/"queue"/
        # "prefill"/"decode": Span} (NULL_SPANs when tracing is off)
        self._req_spans: dict = {}
        # _round counts COMPLETED decode rounds (a fused block advances it
        # by its step count); admission due-ness and sampler keys both
        # index it, which is what keeps any interleaving of block sizes
        # byte-identical to the single-step engine
        self._round = 0

    def _place(self, cache):
        """device_put a freshly built cache onto its mesh shardings."""
        if self.mesh is None:
            return cache
        return jax.device_put(cache, api.cache_shardings(
            self.cfg, cache, self.mesh, seq_shard=False))

    # -- public API ------------------------------------------------------
    @property
    def queue(self) -> List[Request]:
        return self.sched._q

    def submit(self, req: Request) -> None:
        if len(req.prompt) >= self.max_len:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens leaves no decode room "
                f"in a max_len={self.max_len} cache")
        if not req.t_submit:
            req.t_submit = time.perf_counter()
        if self.trace.enabled:
            track = f"req/{req.uid}"
            self._req_spans[req.uid] = {
                "request": self.trace.begin(
                    "request", track,
                    {"uid": req.uid, "prompt_tokens": len(req.prompt)}),
                "queue": self.trace.begin("queue", track),
            }
        self.sched.submit(req)

    def step(self) -> List[Request]:
        """One scheduling iteration: admit if due (per the interleaving
        policy), then decode — one token per live slot, or up to
        ``decode_block`` tokens in one fused on-device loop.  Returns the
        requests that finished this step.  Wall time accrues HERE, so
        ``step()``-driven callers (benchmarks, the serve CLI loop) get the
        same tok/s accounting as ``run()``."""
        t0 = time.perf_counter()
        step_span = self.trace.begin("step", args={"round": self._round})
        finished: List[Request] = []
        try:
            if self._pool:
                # splice any in-flight admissions whose results came
                # ready since the last boundary; when nothing is live
                # decode can't make progress, so block on the pool head
                # instead of spinning
                finished.extend(self._drain_pool(
                    block=not any(r is not None for r in self.live)))
            if self._round % self.admit_every == 0 or not self._occupied():
                with self.trace.span("admit"):
                    finished.extend(self._admit())
            if any(self.live):
                finished.extend(self._decode_rounds())
            else:
                self._round += 1     # idle step still advances the clock
            return finished
        finally:
            step_span.end(finished=len(finished))
            self.stats.wall_s += time.perf_counter() - t0

    def run(self, max_steps: int = 10_000) -> List[Request]:
        finished: List[Request] = []
        for _ in range(max_steps):
            finished.extend(self.step())
            if not self._occupied() and not len(self.sched):
                # drained: no live slot, no in-flight ticket, empty
                # queue — admission on an all-free engine always takes
                # at least the queue head, so this means done.  (A
                # non-empty queue that can NEVER admit raises inside
                # _admit instead of spinning to max_steps — see the
                # capacity-stall check there.)
                break
        return finished

    def _occupied(self) -> bool:
        """Any slot live OR reserved by an in-flight admission ticket."""
        return any(r is not None for r in self.live) \
            or bool(self._reserved.any()) or bool(self._pool)

    # -- internals ---------------------------------------------------------
    def _sample_host(self, logits: Array, stream: int = 0,
                     also: Optional[Array] = None):
        """Host-side sampling (admission first tokens, single-step decode).
        Keyed samplers get ``fold_in(fold_in(key, stream), round)`` —
        stream 0 is the decode stream the fused loop folds on device,
        stream 1 the admission stream — so both decode paths and every
        block interleaving draw the same tokens.  ``also``, a device array
        the same launch produced, comes back in the same readback: the
        call then returns ``(tokens, also)``."""
        # the ONE sanctioned device→host sync in the engine: emitted
        # tokens must land in host lists, so the readback is the point
        with self.trace.span(_READBACK_SPAN[stream]):
            if getattr(self.sampler, "takes_key", False):
                k = jax.random.fold_in(
                    jax.random.fold_in(self._key, stream), self._round)
                tok = self.sampler(logits, 1, k)
            else:
                tok = self.sampler(logits, 1)
            if also is None:
                return np.asarray(tok)  # dcomlint: disable=J2
            tok, also = jax.device_get((tok, also))  # dcomlint: disable=J2
            return np.asarray(tok), np.asarray(also)

    def _stops(self, req: Request) -> frozenset:
        eos = req.eos_id if req.eos_id is not None else self.eos_id
        toks = set(req.stop_tokens)
        if eos is not None:
            toks.add(eos)
        return frozenset(toks)

    def _finish(self, slot: int, req: Request, now: float, *,
                eos: bool) -> None:
        """Free a slot the moment its request stops (token or budget)."""
        req.done = True
        req.t_done = now
        self.live[slot] = None
        self.family.free_slot(slot)
        if eos:
            self.stats.stopped_eos += 1
        else:
            self.stats.stopped_budget += 1
        spans = self._req_spans.pop(req.uid, None)
        if spans:
            # Span.end is idempotent: queue/prefill already ended at their
            # own boundaries; this closes whatever is still open
            for name in ("queue", "prefill", "decode"):
                if name in spans:
                    spans[name].end()
            spans["request"].end(tokens=len(req.out_tokens), eos=eos)

    def _check_stop(self, slot: int, req: Request, now: float) -> bool:
        """Stop-token / budget check after a token was appended."""
        if req.out_tokens and req.out_tokens[-1] in self._stops(req):
            self._finish(slot, req, now, eos=True)
            return True
        if (len(req.out_tokens) >= req.max_new_tokens
                or self.pos[slot] >= self.max_len - 1):
            self._finish(slot, req, now, eos=False)
            return True
        return False

    def _admit(self) -> List[Request]:
        """Admission: drain the queue into the free slots, ONE prefill
        launch per length bucket, so other-bucket requests no longer wait
        behind the head bucket while slots sit idle.  Async mode only
        DISPATCHES here (tickets into the pool); sync/deterministic mode
        completes each ticket inline at its dispatch round."""
        finished: List[Request] = []
        blocked = False
        while True:
            free = [i for i, r in enumerate(self.live)
                    if r is None and not self._reserved[i]]
            if not free or not len(self.sched):
                break
            has_live = any(r is not None for r in self.live)
            if self.admission == "gang" and has_live \
                    and not self.family.gang_live_splice:
                # legacy gang restriction, kept only for the A/B benchmark:
                # splice-merge used to exist for the dense-cache path only
                break
            with self.trace.span("admit.prepare"):
                batch = self.sched.next_batch(len(free))
                if not batch:
                    break
                maxp = max(len(r.prompt) for r in batch)
                plen = self.sched.bucket_of(maxp)
                if plen >= self.max_len:
                    # bucket rounds past the cache: fall back to the
                    # exact length (one extra jit shape near the cap
                    # beats losing decode room)
                    plen = maxp
                # family capacity check (paged: prefix lookups + page
                # reservation — hit refs already held inside ctx); None
                # defers the batch until in-flight work frees resources
                ctx = self.family.reserve(batch, plen)
            if ctx is None:
                self.sched.requeue(batch)
                self.stats.stalls += 1
                blocked = True
                break
            finished.extend(self._admit_batch(batch, free, plen, has_live,
                                              ctx))
            if self.admission == "gang":
                break                # legacy: one gang per admission
        if blocked and not self._occupied():
            # Deferred on capacity with NO live slot and NO in-flight
            # ticket: nothing can ever free resources (a paged
            # reservation already evicted every evictable prefix entry),
            # so retrying would livelock run() until max_steps and
            # silently drop the request.  Fail loudly instead.
            raise RuntimeError(self.family.capacity_msg(self.sched._q[0]))
        return finished

    def _admit_batch(self, batch: List[Request], free: List[int],
                     plen: int, has_live: bool,
                     ctx: Any = None) -> List[Request]:
        """One admission batch: stamp dispatch times, launch the prefill
        (ticket dispatch), then either complete inline (sync and
        deterministic modes — identical device-side program order to the
        pre-split engine) or park the tickets in the ready pool (async
        ``ready`` mode) for ``_drain_pool`` to splice at step edges."""
        slots_idx = free[:len(batch)]
        now = time.perf_counter()
        for req in batch:
            req.t_dispatch = now
            spans = self._req_spans.get(req.uid)
            if spans:
                spans["queue"].end()
                spans["prefill"] = self.trace.begin(
                    "prefill", f"req/{req.uid}", {"plen": plen})
        self.admit_log.extend(r.uid for r in batch)
        self.stats.prefills += len(batch)
        if self.admission == "gang":
            with phase_scope("prefill"):
                logits = self.family.gang(batch, slots_idx, plen, has_live)
            nxt = self._sample_host(logits, stream=1)[slots_idx]
            fls = self.family.frozen_after_prefill(len(batch), plen)
            self.stats.prefill_batches += 1
            return self._activate(batch, slots_idx, plen, nxt, fls)
        for slot in slots_idx:
            self._reserved[slot] = True
        with phase_scope("prefill"):
            tickets = self.family.dispatch(batch, slots_idx, plen, ctx)
        if self.trace.enabled:
            for t in tickets:
                t.span = self.trace.begin(
                    "ticket", "tickets",
                    {"requests": len(t.requests), "plen": t.plen,
                     "uids": [r.uid for r in t.requests]})
        if self.prefill_async and self.ready_order == "ready":
            self._pool.extend(tickets)
            self.stats.prefill_inflight_peak = max(
                self.stats.prefill_inflight_peak, len(self._pool))
            return []
        finished: List[Request] = []
        for t in tickets:
            finished.extend(self._finish_ticket(t))
        return finished

    def _activate(self, batch: List[Request], slots_idx: List[int],
                  plen: int, nxt: np.ndarray,
                  fls: np.ndarray) -> List[Request]:
        """Completion tail shared by every admission path: occupy the
        slots, stamp the TTFT split (queue wait vs prefill compute), and
        apply first-token stop checks."""
        now = time.perf_counter()
        finished: List[Request] = []
        with self.trace.span("admit.activate"):
            for j, (slot, req) in enumerate(zip(slots_idx, batch)):
                self._reserved[slot] = False
                self.live[slot] = req
                self.pos[slot] = plen
                self.frozen_len[slot] = fls[j]
                req.out_tokens.append(int(nxt[j]))
                req.t_first = req.t_last = now
                spans = self._req_spans.get(req.uid)
                if spans:
                    spans["prefill"].end(slot=slot)
                    spans["decode"] = self.trace.begin("decode",
                                                       f"req/{req.uid}")
                self.stats.ttft_s.append(now - req.t_submit)
                self.stats.ttft_queue_s.append(req.t_dispatch - req.t_submit)
                self.stats.ttft_compute_s.append(now - req.t_dispatch)
                # the FIRST token can already be a stop token (or the whole
                # budget): finish and free the slot immediately
                if self._check_stop(slot, req, now):
                    finished.append(req)
        return finished

    def _finish_ticket(self, t: PrefillTicket) -> List[Request]:
        with self.trace.span("splice", args={"requests": len(t.requests)}), \
                phase_scope("splice"):
            nxt, fls = t.complete()
        if t.span is not None:
            t.span.end()
        return self._activate(t.requests, t.slots, t.plen, nxt, fls)

    def _drain_pool(self, *, block: bool) -> List[Request]:
        """Splice finished prefill tickets into their reserved slots.

        Tickets are visited in dispatch order; a ticket is spliced when
        its done-probe reports ready (never blocking decode on an
        in-flight Lanczos).  With ``block=True`` (nothing live to decode,
        so there is no useful work to overlap) the pool HEAD is completed
        even if not yet ready — ``complete()`` then blocks on the device
        result, which is exactly the sync engine's behaviour."""
        finished: List[Request] = []
        rest: List[PrefillTicket] = []
        spliced = 0
        with self.trace.span("drain-pool",
                             args={"pool": len(self._pool)}) as dspan:
            for t in self._pool:
                if (block and not spliced and not rest) or t.ready():
                    finished.extend(self._finish_ticket(t))
                    spliced += 1
                else:
                    rest.append(t)
            dspan.annotate(spliced=spliced)
        self._pool = rest
        return finished

    def cancel_pending(self, requeue: bool = True) -> int:
        """Cancel every in-flight admission ticket.

        Reserved slots are freed, paged tickets release their page refs
        (prefix-hit shared refs exactly once — the ref taken at lookup
        was installed as the slot's block table at dispatch, and
        ``free_slot`` releases it), and the requests re-enter the queue
        in arrival order (``requeue=False`` drops them).  Dispatch-side
        stats are unwound so a cancelled request is not double-counted
        when re-admitted.  The device computation itself is not
        interrupted — its results are simply never spliced.  Returns the
        number of cancelled requests."""
        n = 0
        for t in self._pool:
            t.cancel()
            if t.span is not None:
                t.span.end(cancelled=True)
            for slot in t.slots:
                self._reserved[slot] = False
            self.stats.prefills -= len(t.requests)
            for req in t.requests:
                req.t_dispatch = 0.0
                spans = self._req_spans.get(req.uid)
                if spans:
                    spans.pop("prefill", NULL_SPAN).end(cancelled=True)
                    if requeue:      # back in the queue: reopen its wait
                        spans["queue"] = self.trace.begin(
                            "queue", f"req/{req.uid}", {"requeued": True})
                    else:
                        spans["request"].end(dropped=True)
                        del self._req_spans[req.uid]
                n += 1
                for k in range(len(self.admit_log) - 1, -1, -1):
                    if self.admit_log[k] == req.uid:
                        del self.admit_log[k]
                        break
            if requeue:
                self.sched.requeue(t.requests)
        self._pool = []
        return n

    @staticmethod
    def _padded(batch: List[Request], rows: int, plen: int,
                row_of: Callable[[int], int]) -> np.ndarray:
        """``[rows, plen]`` int32: each prompt left-padded with 0 in row
        ``row_of(j)``; the other rows all padding."""
        toks = np.zeros((rows, plen), np.int32)
        for j, req in enumerate(batch):
            toks[row_of(j), plen - len(req.prompt):] = req.prompt  # left-pad
        return toks

    def _toks(self, batch: List[Request], rows: int, plen: int,
              row_of: Callable[[int], int]) -> np.ndarray:
        """The token matrix of one prefill launch (``_padded``), counted
        as launched."""
        toks = self._padded(batch, rows, plen, row_of)
        self._count_prefill(toks, sum(len(r.prompt) for r in batch))
        return toks

    def _count_prefill(self, toks: np.ndarray, real: int) -> None:
        """Count a launched prefill token matrix holding ``real`` prompt
        tokens: the rest of its entries are bucket and batch padding."""
        self.stats.prefill_tokens["prompt"].inc(real)
        self.stats.prefill_tokens["pad"].inc(toks.size - real)

    def _last_tokens(self) -> np.ndarray:
        tok = np.zeros((self.slots,), np.int32)
        for i, req in enumerate(self.live):
            if req is not None and req.out_tokens:
                tok[i] = req.out_tokens[-1]
        return tok

    def _decode_rounds(self) -> List[Request]:
        """One decode LAUNCH: the single-step round (decode_block == 1,
        bit-identical to the pre-fusion engine) or a fused block of up to
        ``decode_block`` rounds.  Fold checks run here, at the boundary —
        identical cadence either way (a no-op for families whose state
        never grows)."""
        self.family.maybe_fold()
        if self.decode_block <= 1:
            done = self._decode_round()
            self._round += 1
            return done
        return self._decode_block_round()

    def _decode_round(self) -> List[Request]:
        with self.trace.span("decode-step"):
            with self.trace.span("decode.prepare"):
                tok = self._last_tokens()
            with phase_scope("decode"):
                with self.trace.span("decode.launch"):
                    logits = self.family.decode(tok)
                nxt = self._sample_host(logits)
            self.stats.decode_steps += 1
            self.stats.blocks += 1
            now = time.perf_counter()
            done: List[Request] = []
            with self.trace.span("decode.deliver"):
                for i, req in enumerate(self.live):
                    if req is None:
                        continue
                    self.pos[i] += 1
                    req.out_tokens.append(int(nxt[i]))
                    self.stats.tokens_out += 1
                    self.stats.itl_s.append(now - req.t_last)
                    req.t_last = now
                    # EOS / stop tokens end a request the moment they are
                    # emitted (the old loop only stopped on budget or
                    # cache exhaustion, so every request burned its full
                    # max_new_tokens)
                    if self._check_stop(i, req, now):
                        done.append(req)
        return done

    # -- fused block decode ------------------------------------------------
    def _block_len(self) -> int:
        """Steps the next fused block may run before a host-side event is
        due.  Every horizon is DETERMINISTIC from engine state, which is
        the fold/admission half of the token-exactness argument (stop
        tokens — the non-deterministic half — end the block early on
        device instead):

        * budget: no live slot may decode past ``max_new_tokens`` or the
          cache end (the single-step engine would have finished it);
        * fold: the family's ``fold_horizon()`` — steps until some tail
          fills (folds only happen at boundaries, at the exact same
          occupancy); None for families whose state never grows;
        * admission: with ``admit_every > 1`` and a non-empty queue, stop
          at the next due round.  With ``admit_every == 1`` no cap is
          needed — a queued request that admission just deferred (no free
          slot, bucket mismatch, page pressure) can only be unblocked by
          a slot freeing or a fold, which are boundary events themselves.
        """
        blk = self.decode_block
        for i, req in enumerate(self.live):
            if req is None:
                continue
            blk = min(blk,
                      req.max_new_tokens - len(req.out_tokens),
                      (self.max_len - 1) - int(self.pos[i]))
        fh = self.family.fold_horizon()
        if fh is not None:
            blk = min(blk, fh)
        if len(self.sched) and self.admit_every > 1:
            due = (self._round // self.admit_every + 1) * self.admit_every
            blk = min(blk, due - self._round)
        return max(1, blk)

    def _stop_table(self) -> np.ndarray:
        """Per-slot stop-token table for the on-device early-exit check:
        int32 [slots, W], −1-padded (dead slots are all −1, matching no
        sampled token).  W is the widest live stop set, so the jit shape
        only changes when a request carries more stop tokens than any
        before it."""
        sets = [sorted(self._stops(r)) if r is not None else []
                for r in self.live]
        w = max([len(s) for s in sets] + [1])
        tbl = np.full((self.slots, w), -1, np.int32)
        for i, s in enumerate(sets):
            tbl[i, :len(s)] = s
        return tbl

    def _decode_block_round(self) -> List[Request]:
        with self.trace.span("decode-block") as bspan:
            with self.trace.span("decode.prepare"):
                blk = self._block_len()
                tok = self._last_tokens()
                stops = np.asarray(self._stop_table())
                key = jax.random.fold_in(self._key, 0)  # decode stream
                n, r0 = np.int32(blk), np.int32(self._round)
            with phase_scope("decode"):
                with self.trace.span("decode.launch"):
                    buf, steps = self.family.decode_block(tok, n, stops,
                                                          key, r0)
                with self.trace.span("decode.readback"):
                    steps = int(steps)
                    toks = np.asarray(buf)[:steps]  # [steps, slots], syncs
            bspan.annotate(max_steps=blk, steps=steps)
            now = time.perf_counter()
            self.stats.decode_steps += steps
            self.stats.blocks += 1
            self._round += steps
            done: List[Request] = []
            with self.trace.span("decode.deliver"):
                for i, req in enumerate(self.live):
                    if req is None:
                        continue
                    req.out_tokens.extend(int(t) for t in toks[:, i])
                    self.pos[i] += steps
                    self.stats.tokens_out += steps
                    # ITL under block decode: the time since the slot's
                    # previous token, host time between launches included,
                    # spread evenly over the block's tokens (a per-round
                    # stamp would collapse to ~0 for all but the first
                    # token of a block and overstate the first)
                    self.stats.itl_s.extend(
                        [(now - req.t_last) / max(steps, 1)] * steps)
                    req.t_last = now
                    # stops can only sit on the block's LAST step (early
                    # exit), so the boundary check sees exactly what the
                    # single-step engine's per-round check would have
                    if self._check_stop(i, req, now):
                        done.append(req)
        return done
