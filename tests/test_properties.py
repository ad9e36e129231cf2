"""Property-based invariants (hypothesis).

This module holds every hypothesis-driven case so the rest of the suite
imports without the dependency; the importorskip below skips the whole file
when hypothesis is absent (install via requirements-dev.txt).
"""
import jax
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (decompose, decompose_weight, from_dense_svd,
                        lowrank_matmul, lowrank_x_lowrank_weight,
                        relative_error)
from repro.serving import Engine, Request, Scheduler


@settings(max_examples=15, deadline=None)
@given(s=st.integers(12, 48), h=st.integers(12, 48), r=st.integers(1, 6))
def test_property_reconstruction_bounded(s, h, r):
    """‖X − X̂_r‖ ≤ ‖X‖ and ε decreases vs the oracle's tail energy."""
    a = jax.random.normal(jax.random.PRNGKey(s * 1000 + h), (s, h))
    lr = decompose(a, rank=r, iters=min(r + 6, min(s, h)))
    err = float(relative_error(lr, a))
    assert 0.0 <= err <= 1.0 + 1e-3
    # oracle tail: optimal error for the same rank (Eckart–Young)
    sv = np.linalg.svd(np.asarray(a), compute_uv=False)
    opt = float(np.sqrt((sv[r:] ** 2).sum() / (sv ** 2).sum()))
    assert err >= opt - 1e-3            # can't beat optimal
    assert err <= opt + 0.35            # near-optimal for random matrices


@settings(max_examples=12, deadline=None)
@given(s=st.integers(8, 40), h=st.sampled_from([16, 32, 48]),
       n=st.sampled_from([16, 24, 40]), r=st.integers(1, 8),
       bias=st.booleans())
def test_property_eq6_exactness(s, h, n, r, bias):
    """lowrank_matmul(lr, W) reconstructs to lr.reconstruct() @ W (+b) for
    arbitrary shapes/ranks/bias — the Eq. 6 invariant."""
    key = jax.random.PRNGKey(s * 10007 + h * 101 + n)
    lr = from_dense_svd(jax.random.normal(key, (s, h)), r)
    w = jax.random.normal(jax.random.PRNGKey(7), (h, n)) * 0.2
    b = jax.random.normal(jax.random.PRNGKey(8), (n,)) if bias else None
    y = lowrank_matmul(lr, w, bias=b)
    want = lr.reconstruct() @ w + (b if bias else 0.0)
    np.testing.assert_allclose(np.asarray(y.reconstruct()),
                               np.asarray(want), rtol=2e-3, atol=2e-3)
    assert y.vt.shape[-1] == n                     # output stays factored
    assert y.u.shape[-2] == s


@settings(max_examples=10, deadline=None)
@given(s=st.integers(8, 32), h=st.sampled_from([16, 32]),
       r=st.integers(1, 6), p=st.integers(2, 8))
def test_property_eq7_exactness(s, h, r, p):
    """Input+weight preserved product equals the dense double product."""
    key = jax.random.PRNGKey(s * 31 + h * 7 + r)
    lr = from_dense_svd(jax.random.normal(key, (s, h)), r)
    w = jax.random.normal(jax.random.PRNGKey(5), (h, h)) * 0.2
    w_lr = decompose_weight(w, min(p, h))
    y = lowrank_x_lowrank_weight(lr, w_lr)
    want = lr.reconstruct() @ w_lr.reconstruct()
    np.testing.assert_allclose(np.asarray(y.reconstruct()),
                               np.asarray(want), rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Serving-scheduler invariants (pure python — no device work)
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(lens=st.lists(st.integers(1, 40), min_size=1, max_size=20),
       bucket=st.sampled_from([1, 4, 16]),
       max_admit=st.sampled_from([0, 2]),
       frees=st.lists(st.integers(0, 4), min_size=1, max_size=30))
def test_property_scheduler_fifo_within_bucket(lens, bucket, max_admit,
                                               frees):
    """Every submitted request is dispatched exactly once, each batch is a
    single prefill-length bucket capped at the free-slot count, and
    dispatch order within a bucket is submission (FIFO) order."""
    sched = Scheduler(bucket=bucket, max_admit=max_admit)
    reqs = [Request(uid=i, prompt=np.zeros(n, np.int32))
            for i, n in enumerate(lens)]
    for r in reqs:
        sched.submit(r)
    dispatched = []
    for f in frees + [4] * len(reqs):          # drain with full freedom
        batch = sched.next_batch(f)
        assert len(batch) <= f
        if max_admit:
            assert len(batch) <= max_admit
        assert len({sched.bucket_of(len(r.prompt)) for r in batch}) <= 1
        dispatched += batch
        if not len(sched):
            break
    assert sorted(r.uid for r in dispatched) == [r.uid for r in reqs]
    by_bucket = {}
    for r in dispatched:
        by_bucket.setdefault(sched.bucket_of(len(r.prompt)), []).append(r.uid)
    for uids in by_bucket.values():
        assert uids == sorted(uids), "FIFO violated within a bucket"


_MODEL = {}

# one reduced arch per serving family the engine properties draw from;
# MoE pins capacity_factor so the router is batch-size-invariant (a
# capacity-dropped token routes differently between interleavings by
# design — see test_serving_conformance._family_model)
_PROP_ARCHS = {"dense": "llama2-7b", "ssm": "mamba2-780m",
               "moe": "olmoe-1b-7b"}


def _family_model(family):
    if family not in _MODEL:
        import jax as _jax
        from repro.configs import all_archs
        from repro.models import model_fns
        cfg = all_archs()[_PROP_ARCHS[family]].reduced()
        if family == "moe":
            cfg = cfg.replace(capacity_factor=8.0)
        _MODEL[family] = (cfg,
                          model_fns(cfg).init(_jax.random.PRNGKey(0), cfg))
    return _MODEL[family]


def _dense_model():
    return _family_model("dense")


@settings(max_examples=5, deadline=None)
@given(data=st.data())
def test_property_engine_finishes_once_no_leaks_monotone(data):
    """Engine invariants under random arrivals FOR ANY SERVING FAMILY:
    every submitted request finishes exactly once, no slot leaks, and
    while a slot keeps its occupant its ``pos`` strictly advances and
    ``frozen_len`` never shrinks (per-slot monotonicity).  The dkv and
    paged layouts only exist for the dense family's KV cache."""
    family = data.draw(st.sampled_from(["dense", "ssm", "moe"]))
    cfg, params = _family_model(family)
    n = data.draw(st.integers(1, 5))
    lens = data.draw(st.lists(st.integers(1, 12), min_size=n, max_size=n))
    news = data.draw(st.lists(st.integers(1, 4), min_size=n, max_size=n))
    arrive = sorted(data.draw(st.lists(st.integers(0, 6), min_size=n,
                                       max_size=n)))
    dkv = family == "dense" and data.draw(st.booleans())
    paged = dkv and data.draw(st.booleans())
    kw = dict(decompose_kv_rank=6, dkv_tail=2, paged=paged) if dkv else {}
    eng = Engine(cfg, params, slots=2, max_len=48, **kw)
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab, l,
                                              dtype=np.int32),
                    max_new_tokens=m)
            for i, (l, m) in enumerate(zip(lens, news))]
    pending = list(zip(arrive, reqs))
    finished = []
    for step in range(300):
        while pending and pending[0][0] <= step:
            eng.submit(pending.pop(0)[1])
        occ = [id(r) if r is not None else None for r in eng.live]
        pos0, fr0 = eng.pos.copy(), eng.frozen_len.copy()
        finished += eng.step()
        for s in range(eng.slots):
            if occ[s] is not None and eng.live[s] is not None \
                    and id(eng.live[s]) == occ[s]:
                assert eng.pos[s] > pos0[s], "pos stalled on a live slot"
                assert eng.frozen_len[s] >= fr0[s], "frozen_len shrank"
        if not pending and not len(eng.sched) and not any(eng.live):
            break
    assert sorted(r.uid for r in finished) == list(range(n))
    assert all(r.done for r in finished)
    assert eng.live == [None] * eng.slots, "slot leak"
    assert eng.stats.prefills == n
    if paged:                        # every page returned after drain
        assert eng.pager.alloc.free_pages == eng.pager.num_pages - 1
        assert eng.pager.talloc.free_pages == eng.pager.num_tail_pages - 1


@settings(max_examples=5, deadline=None)
@given(data=st.data())
def test_property_block_interleaving_token_exact(data):
    """ANY per-step interleaving of fused decode-block lengths yields
    byte-identical tokens to the single-step engine: the block length is
    pure execution strategy (how many rounds one launch covers), never
    semantics.  Exercises the dkv (and optionally paged) engine across
    fold boundaries and organic re-admissions (slots < requests)."""
    cfg, params = _dense_model()
    paged = data.draw(st.booleans())
    tail = data.draw(st.sampled_from([2, 4]))

    def serve(blocks=None):
        eng = Engine(cfg, params, slots=2, max_len=48,
                     decompose_kv_rank=6, dkv_tail=tail, paged=paged)
        rng = np.random.RandomState(0)
        for i in range(3):
            eng.submit(Request(uid=i,
                               prompt=rng.randint(0, cfg.vocab, 8,
                                                  dtype=np.int32),
                               max_new_tokens=6))
        done = []
        for _ in range(300):
            if blocks is not None:
                # decode_block is re-readable every step: draw a fresh
                # length for each launch (capped at the fold horizon,
                # as Engine.__init__ does)
                eng.decode_block = min(tail, blocks.draw(
                    st.sampled_from([1, 2, 3, 4, 8])))
            done.extend(eng.step())
            if not any(eng.live) and not len(eng.sched):
                break
        assert sorted(r.uid for r in done) == [0, 1, 2]
        return {r.uid: r.out_tokens for r in done}

    base = serve(None)                   # decode_block=1 single-step
    assert serve(data) == base, "block interleaving changed tokens"


# ---------------------------------------------------------------------------
# Page-allocator invariants (pure python — no device work)
# ---------------------------------------------------------------------------

from repro.serving.paged import PageAllocator  # noqa: E402


@settings(max_examples=60, deadline=None)
@given(data=st.data())
def test_property_page_allocator_refcounts_no_leaks(data):
    """Under random alloc/ref/release traffic: page 0 (the write sink) is
    never handed out, no page is ever handed to two owners at once,
    conservation holds (free + live == pool), releasing an unallocated
    page raises (double-free guard), and a full drain returns EVERY page
    to the free list."""
    n = data.draw(st.integers(2, 48))
    al = PageAllocator(n)
    total = n - 1
    held = []                     # (pages, extra_refs) per allocation
    for _ in range(data.draw(st.integers(1, 80))):
        op = data.draw(st.sampled_from(["alloc", "ref", "release",
                                        "release"]))
        if op == "alloc":
            k = data.draw(st.integers(0, total))
            got = al.alloc(k)
            if got is None:
                assert k > 0          # alloc(0) always succeeds
            else:
                assert len(got) == k and 0 not in got
                live = [p for pages, _ in held for p in pages]
                assert not set(got) & set(live), "page double-handed"
                held.append((got, 0))
        elif op == "ref" and held:
            i = data.draw(st.integers(0, len(held) - 1))
            pages, extra = held[i]
            if pages:
                al.ref(pages)
                held[i] = (pages, extra + 1)
        elif op == "release" and held:
            i = data.draw(st.integers(0, len(held) - 1))
            pages, extra = held[i]
            al.release(pages)
            if extra:
                held[i] = (pages, extra - 1)
            else:
                held.pop(i)
        live_count = len({p for pages, _ in held for p in pages})
        assert al.free_pages + live_count == total, "page conservation"
    # drain: release every remaining ref; the pool must come back whole
    for pages, extra in held:
        for _ in range(extra + 1):
            al.release(pages)
    assert al.free_pages == total, "leaked pages after drain"
    assert not al.live_refs
    with pytest.raises(ValueError):
        al.release([1])               # double free raises


@settings(max_examples=30, deadline=None)
@given(lens=st.lists(st.integers(5, 24), min_size=1, max_size=6),
       page=st.sampled_from([2, 4, 8]), cap=st.integers(1, 3))
def test_property_prefix_cache_capacity_and_refs(lens, page, cap):
    """PrefixCache never exceeds its capacity, holds exactly one ref per
    page of each live entry, and dropping every entry returns the pool to
    its pre-insert state."""
    from repro.serving.paged import PrefixCache
    al = PageAllocator(256)
    pc = PrefixCache(cap, page, al)
    slots = []
    rng = np.random.RandomState(0)
    for n in lens:
        toks = rng.randint(0, 100, n).astype(np.int32)
        pages = al.alloc(-(-n // page))
        pc.insert(toks, pages, None, None, r_eff=4)
        slots.append(pages)
    assert len(pc) <= cap
    want = sum(len(e.pages) for e in pc._entries.values())
    # slots still hold their own refs; entry refs are ON TOP of them
    over = sum(rc - 1 for rc in al.live_refs.values())
    assert over == want, "entries must hold exactly one ref per page"
    pc.drop_all()
    assert sum(rc - 1 for rc in al.live_refs.values()) == 0
    for pages in slots:
        al.release(pages)
    assert al.free_pages == 255


# ---------------------------------------------------------------------------
# Tuner cost-model invariant (pure python — no device work)
# ---------------------------------------------------------------------------

from repro import tune as _tune  # noqa: E402


@settings(max_examples=40, deadline=None)
@given(b=st.integers(1, 8), s=st.integers(1, 700), h=st.integers(1, 700),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       kernel=st.sampled_from(["lanczos_reorth", "matvec_expand",
                               "lowrank_matmul", "dkv_attention"]),
       dev=st.sampled_from([_tune.V5E, _tune.CPU_INTERPRET]))
def test_property_cost_model_u_shaped_in_f(b, s, h, dtype, kernel, dev):
    """The predicted latency is U-shaped (unimodal) in the expansion
    factor along the power-of-two grid for EVERY shape/dtype/device:
    non-increasing up to its argmin, non-decreasing after.  This is the
    structural property the pruner relies on — a non-unimodal model could
    prune away the true optimum."""
    shape = {"lanczos_reorth": (b, s, h),
             "matvec_expand": (s, h),
             "lowrank_matmul": (max(1, 2 * b), s, h),
             "dkv_attention": (b, s, h)}[kernel]
    grid = sorted(_tune.get_space(kernel).param("expansion").choices)
    ts = [_tune.predict(kernel, shape, dtype, {"expansion": f}, dev)
          for f in grid]
    assert all(t > 0 for t in ts)
    i = min(range(len(ts)), key=ts.__getitem__)
    for j in range(i):
        assert ts[j] >= ts[j + 1] * (1 - 1e-9), \
            (grid, ts, "not non-increasing left of argmin")
    for j in range(i, len(ts) - 1):
        assert ts[j] <= ts[j + 1] * (1 + 1e-9), \
            (grid, ts, "not non-decreasing right of argmin")


# ---------------------------------------------------------------------------
# Sharding rule invariants (8-device host-serving mesh)
# ---------------------------------------------------------------------------

from jax.sharding import AbstractMesh, PartitionSpec as ShP

from repro.distributed import sharding as _sh

_SMESHES = [AbstractMesh((8, 1), ("data", "model")),
            AbstractMesh((2, 4), ("data", "model")),
            AbstractMesh((2, 2, 2), ("pod", "data", "model"))]


def _axis_sz(mesh, axis):
    return _sh.axis_size(mesh, axis)


@settings(max_examples=40, deadline=None)
@given(mesh_i=st.integers(0, len(_SMESHES) - 1),
       name=st.sampled_from(["k", "v", "k_u", "v_u", "k_vt", "v_vt",
                             "conv", "ssm"]),
       dims=st.lists(st.integers(1, 24), min_size=3, max_size=5))
def test_property_cache_spec_dims_always_divide(mesh_i, name, dims):
    """Every axis cache_pspec shards divides its mesh axis exactly — the
    divisibility guard holds for EVERY leaf family and ANY shape, so a
    mesh-serving engine can never be handed an unshardable cache."""
    mesh = _SMESHES[mesh_i]
    nd_min = {"k": 4, "v": 4, "ssm": 4}.get(name, 3)
    shape = tuple(dims[:max(nd_min, len(dims))])
    if len(shape) < nd_min:
        shape = shape + (8,) * (nd_min - len(shape))
    spec = _sh.cache_pspec(name, shape, mesh)
    assert len(spec) == len(shape)
    for dim, axis in zip(shape, spec):
        if axis is not None:
            assert dim % _axis_sz(mesh, axis) == 0, (name, shape, spec)


@settings(max_examples=40, deadline=None)
@given(mesh_i=st.integers(0, len(_SMESHES) - 1),
       b=st.integers(1, 32), t=st.integers(1, 64), r=st.integers(1, 16))
def test_property_dkv_u_time_axis_model_replicated(mesh_i, b, t, r):
    """k_u/v_u NEVER shard over "model" (the refuted §Perf C3 layout), and
    batch-1 caches shard time over "data" exactly when it divides."""
    mesh = _SMESHES[mesh_i]
    spec = _sh.cache_pspec("k_u", (4, b, t, r), mesh)
    assert "model" not in jax.tree_util.tree_leaves(list(spec))
    if b == 1:
        expect = "data" if t % mesh.shape["data"] == 0 else None
        assert spec[2] == expect
    dp_sz = _axis_sz(mesh, _sh.dp_axes(mesh))
    if b > 1 and b % dp_sz == 0:
        assert spec[1] == _sh.dp_name(mesh)


@settings(max_examples=40, deadline=None)
@given(mesh_i=st.integers(0, len(_SMESHES) - 1),
       dims=st.lists(st.integers(1, 64), min_size=1, max_size=4),
       presharded=st.booleans())
def test_property_zero1_first_divisible_dim(mesh_i, dims, presharded):
    """_zero1 adds the DP axis to exactly the FIRST unsharded dim that
    divides the DP size (and is > 1); all other dims keep their spec."""
    mesh = _SMESHES[mesh_i]
    shape = tuple(dims)
    base = [None] * len(shape)
    if presharded and len(shape) and shape[-1] % mesh.shape["model"] == 0:
        base[-1] = "model"
    spec = _sh._zero1(ShP(*base), shape, mesh)
    dp = _sh.dp_axes(mesh)
    dp_sz = _axis_sz(mesh, dp)
    dp_entry = _sh.dp_name(mesh)
    expect_i = next((i for i, (d, s) in enumerate(zip(shape, base))
                     if s is None and d % dp_sz == 0 and d > 1), None)
    for i, (s0, s1) in enumerate(zip(base, spec)):
        if i == expect_i:
            assert s1 == dp_entry
        else:
            assert s1 == s0, (shape, base, spec)


@settings(max_examples=6, deadline=None)
@given(data=st.data())
def test_property_async_engine_interleavings(data):
    """Arbitrary interleavings of submit / dispatch / defer / ready /
    stop events against the ASYNC serving engine (the defers arise
    organically from a deliberately tight page pool, the ready/splice
    timing from the ticket pool): slot and page conservation after
    drain, FIFO-per-bucket dispatch order, and token exactness vs the
    synchronous engine in deterministic ready-order mode.  The family
    draw runs the same interleavings through the O(1)-state SSM engine
    (no dkv, no pages — ticket/splice machinery is family-generic)."""
    family = data.draw(st.sampled_from(["dense", "ssm"]))
    cfg, params = _family_model(family)
    n = data.draw(st.integers(1, 5))
    lens = data.draw(st.lists(st.integers(1, 20), min_size=n, max_size=n))
    news = data.draw(st.lists(st.integers(1, 4), min_size=n, max_size=n))
    arrive = sorted(data.draw(st.lists(st.integers(0, 6), min_size=n,
                                       max_size=n)))
    paged = family == "dense" and data.draw(st.booleans())
    mode = data.draw(st.sampled_from(["deterministic", "ready"]))
    block = data.draw(st.sampled_from([1, 3]))

    from repro.engine import DecomposeEngine, EngineConfig

    def build(**extra):
        # dkv_tail=8 > max_new keeps folds out of the picture so the
        # tight pool (kv_pool_pages=3: two real pages) produces DEFER
        # events, never fold-exhaustion; sched_max_admit=1 keeps every
        # single batch satisfiable (a lone bucket-32 prompt needs both
        # pages), so a defer always resolves when a slot frees
        deng = DecomposeEngine(EngineConfig(
            kv_rank=6, kv_tail=8, kv_page=16,
            kv_pool_pages=3 if paged else 0, sched_max_admit=1,
            decode_block=block))
        # an explicit rank-0 keeps the SSM engine on its family cache
        # (the engine config still supplies sched/block knobs)
        fam_kw = {} if family == "dense" else dict(decompose_kv_rank=0)
        return Engine(cfg, params, slots=2, max_len=48, paged=paged,
                      decompose_engine=deng, **fam_kw, **extra)

    def drive(eng):
        rng = np.random.RandomState(0)
        reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab, l,
                                                  dtype=np.int32),
                        max_new_tokens=m)
                for i, (l, m) in enumerate(zip(lens, news))]
        pending = list(zip(arrive, reqs))
        out = {}
        for step in range(400):
            while pending and pending[0][0] <= step:
                eng.submit(pending.pop(0)[1])
            for r in eng.step():
                out[r.uid] = list(r.out_tokens)
            if not pending and not eng._occupied() and not len(eng.sched):
                break
        return out

    sync = drive(build())
    eng = build(prefill_async=True, ready_order=mode)
    got = drive(eng)
    assert sorted(got) == sorted(sync) == list(range(n))
    if mode == "deterministic":
        assert got == sync, "det mode must be byte-identical to sync"
    # conservation after drain: no ticket, no reserved slot, no leaked page
    assert not eng._pool and not eng._reserved.any()
    assert eng.live == [None] * eng.slots
    if paged:
        assert eng.pager.alloc.free_pages == eng.pager.num_pages - 1
        assert eng.pager.talloc.free_pages == eng.pager.num_tail_pages - 1
    # dispatch order is FIFO within each prompt-length bucket
    sched = eng.sched
    by_bucket = {}
    uid_len = {i: l for i, l in enumerate(lens)}
    for uid in eng.admit_log:
        by_bucket.setdefault(sched.bucket_of(uid_len[uid]), []).append(uid)
    for uids in by_bucket.values():
        assert uids == sorted(uids), "dispatch order broke bucket FIFO"


@settings(max_examples=60, deadline=None)
@given(data=st.data())
def test_property_histogram_quantiles_within_bucket_error(data):
    """The log-bucketed streaming histogram's quantiles match
    numpy.percentile(inverted_cdf) to within half a bucket of relative
    error (10^(1/(2·BPD)) − 1 ≈ 5.9%) on ANY positive sample set —
    arbitrary scale, arbitrary skew, duplicates, single elements."""
    from repro.obs import BUCKETS_PER_DECADE
    from repro.obs.registry import Histogram
    qerr = 10.0 ** (0.5 / BUCKETS_PER_DECADE) - 1.0
    scale = data.draw(st.sampled_from([1e-6, 1e-3, 1.0, 1e3]),
                      label="scale")
    xs = data.draw(st.lists(
        st.floats(min_value=1e-3, max_value=1e3, allow_nan=False,
                  allow_infinity=False),
        min_size=1, max_size=300), label="samples")
    xs = [v * scale for v in xs]
    h = Histogram("x")
    for v in xs:
        h.observe(v)
    q = data.draw(st.floats(min_value=0.01, max_value=1.0), label="q")
    got = h.quantile(q)
    exact = float(np.percentile(np.asarray(xs), 100.0 * q,
                                method="inverted_cdf"))
    # the +1e-9·exact ULP slack covers samples landing EXACTLY on a
    # bucket edge, where the error ties qerr·exact to the last bit
    assert abs(got - exact) <= (qerr + 1e-9) * exact + 1e-15, \
        f"q={q}: hist {got} vs exact {exact} (n={len(xs)})"
    # quantiles are monotone in q and clamped to the observed range
    # (q=1.0 is the top bucket's midpoint: ≤ max, within qerr below it)
    assert h.min - 1e-15 <= h.quantile(0.0)
    assert h.max * (1 - qerr) - 1e-15 <= h.quantile(1.0) <= h.max + 1e-15
    qs = [h.quantile(t / 10) for t in range(11)]
    assert all(a <= b + 1e-15 for a, b in zip(qs, qs[1:]))
