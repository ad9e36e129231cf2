"""Serving engine: continuous batching, slot reuse, stats."""
import jax
import numpy as np
import pytest

from repro.configs import all_archs
from repro.models import model_fns
from repro.serving import Engine, Request


def _engine(slots=2, max_len=48):
    cfg = all_archs()["llama2-7b"].reduced()
    fns = model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    return cfg, Engine(cfg, params, slots=slots, max_len=max_len)


def test_completes_all_requests():
    cfg, eng = _engine()
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i, prompt=rng.randint(0, cfg.vocab, 8,
                                              dtype=np.int32),
                    max_new_tokens=4) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 5
    assert all(len(r.out_tokens) >= 4 for r in done)
    assert all(0 <= t < cfg.padded_vocab for r in done for t in r.out_tokens)


def test_continuous_batching_reuses_slots():
    cfg, eng = _engine(slots=2)
    rng = np.random.RandomState(1)
    for i in range(6):
        eng.submit(Request(uid=i, prompt=rng.randint(0, cfg.vocab, 4,
                                                     dtype=np.int32),
                           max_new_tokens=3))
    done = eng.run()
    assert len(done) == 6
    assert eng.stats.prefills == 6      # counted PER REQUEST, not per gang
    assert eng.stats.prefill_batches >= 3   # 6 requests / 2 slots
    assert eng.stats.tokens_out > 0
    assert len(eng.stats.ttft_s) == 6   # one first-token latency each
    assert eng.stats.mean_ttft_s > 0.0


def test_deterministic_outputs():
    cfg, e1 = _engine()
    _, e2 = _engine()
    prompt = np.arange(8, dtype=np.int32)
    for e in (e1, e2):
        e.submit(Request(uid=0, prompt=prompt, max_new_tokens=5))
    o1 = e1.run()[0].out_tokens
    o2 = e2.run()[0].out_tokens
    assert o1 == o2


def test_admission_preserves_live_sequences():
    """Admitting new requests must not corrupt in-flight KV (splice path)."""
    cfg, eng_mixed = _engine(slots=2, max_len=64)
    prompt = np.arange(8, dtype=np.int32)
    # reference: run the long request ALONE
    _, eng_solo = _engine(slots=2, max_len=64)
    eng_solo.submit(Request(uid=0, prompt=prompt, max_new_tokens=10))
    solo = eng_solo.run()[0].out_tokens
    # mixed: same long request + a short one admitted mid-flight
    eng_mixed.submit(Request(uid=0, prompt=prompt, max_new_tokens=10))
    eng_mixed.submit(Request(uid=1, prompt=prompt[:4], max_new_tokens=2))
    # force staggered admission: only one free slot at t=0
    eng_mixed.live[1] = Request(uid=99, prompt=prompt[:2], max_new_tokens=3)
    eng_mixed.pos[1] = 2
    out = {r.uid: r.out_tokens for r in eng_mixed.run()}
    assert out[0] == solo, "live sequence corrupted by later admission"


def test_decomposed_kv_serving():
    """Engine on the low-rank KV cache completes requests + compacts tail."""
    import jax

    from repro.configs import all_archs
    from repro.models import model_fns
    cfg = all_archs()["deepseek-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, slots=2, max_len=64,
                 decompose_kv_rank=8, dkv_tail=4)
    rng = np.random.RandomState(0)
    for i in range(2):
        eng.submit(Request(uid=i, prompt=rng.randint(0, cfg.vocab, 12,
                                                     dtype=np.int32),
                           max_new_tokens=10))   # > tail => compaction runs
    done = eng.run()
    assert len(done) == 2
    assert all(len(r.out_tokens) >= 10 for r in done)
    # frozen_len is PER SLOT now; both slots folded their tail at least once
    assert (eng.frozen_len > 12).all()
    assert eng.stats.tail_folds >= 2


def test_bucket_never_rounds_past_max_len():
    """A prompt that fits in max_len must get its full decode budget even
    when its scheduler bucket would round past the cache length."""
    cfg, eng = _engine(slots=2, max_len=60)   # not a bucket multiple
    assert eng.sched.bucket_of(50) > eng.max_len - 1
    eng.submit(Request(uid=0, prompt=np.arange(50, dtype=np.int32) % cfg.vocab,
                       max_new_tokens=4))
    done = eng.run()
    assert len(done) == 1 and len(done[0].out_tokens) >= 4


def test_oversized_prompt_rejected_at_submit():
    cfg, eng = _engine(slots=1, max_len=32)
    import pytest
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=np.zeros(32, np.int32)))


# ---------------------------------------------------------------------------
# Decode-loop correctness fixes (PR 5)
# ---------------------------------------------------------------------------

def _const_sampler(tok):
    import jax.numpy as jnp
    return lambda lg, k: jnp.full((lg.shape[0],), tok, jnp.int32)


def test_eos_stops_request_and_frees_slot():
    """A request finishes the moment it emits eos_id — not after burning
    its whole max_new_tokens budget — and its slot frees immediately."""
    cfg, eng = _engine(slots=2)
    eng.sampler = _const_sampler(7)
    eng.eos_id = 7
    eng.submit(Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                       max_new_tokens=50))
    done = eng.run()
    assert len(done) == 1 and done[0].done
    assert done[0].out_tokens == [7]     # stopped at the very first token
    assert eng.live == [None] * 2        # slot freed at once
    assert eng.stats.stopped_eos == 1
    assert eng.stats.stopped_budget == 0


def test_per_request_stop_tokens_and_budget_counters():
    """Request-level eos/stop_tokens override the engine default; finishes
    are attributed to stopped_eos vs stopped_budget correctly."""
    cfg, eng = _engine(slots=2)
    eng.sampler = _const_sampler(9)
    eng.submit(Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                       max_new_tokens=40, stop_tokens=(9,)))
    eng.submit(Request(uid=1, prompt=np.arange(6, dtype=np.int32),
                       max_new_tokens=3))        # no stop: runs its budget
    done = {r.uid: r for r in eng.run()}
    assert done[0].out_tokens == [9]
    assert len(done[1].out_tokens) == 3
    assert eng.stats.stopped_eos == 1
    assert eng.stats.stopped_budget == 1


def test_wall_s_accrues_per_step():
    """step()-driven callers (benchmarks, the serve CLI) must see real
    wall time — the old accounting lived only inside run() and reported
    tok/s = inf everywhere else."""
    cfg, eng = _engine(slots=2)
    eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=4))
    done = []
    while len(done) < 1:
        done.extend(eng.step())
    assert eng.stats.wall_s > 0.0
    assert eng.stats.tokens_out / eng.stats.wall_s < float("inf")


def test_multi_bucket_admission_fills_free_slots():
    """A mixed-length queue no longer idles free slots behind the head
    request's bucket: one admission drains further buckets (one prefill
    launch per bucket)."""
    cfg = all_archs()["llama2-7b"].reduced()
    fns = model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, slots=4, max_len=96)
    rng = np.random.RandomState(0)
    for i, n in enumerate((4, 4, 20, 20)):       # two plen buckets
        eng.submit(Request(uid=i, prompt=rng.randint(0, cfg.vocab, n,
                                                     dtype=np.int32),
                           max_new_tokens=3))
    assert eng.sched.bucket_of(4) != eng.sched.bucket_of(20)
    eng.step()
    assert sum(r is not None for r in eng.live) == 4, \
        "free slots idled while another bucket waited"
    assert eng.stats.prefill_batches == 2        # one launch per bucket
    done = eng.run()
    assert sorted(r.uid for r in done) == [0, 1, 2, 3]


def test_fold_retruncates_back_to_configured_kv_rank():
    """Regression for the rank ratchet: after a wider-rank splice (e.g. a
    migrated cache or a config change), the next fold retruncates every
    folding slot back to the configured kv_rank and the engine slices the
    rank axis down once no live slot needs the extra width."""
    from repro.models import decomposed_kv as DK
    cfg = all_archs()["deepseek-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, slots=2, max_len=64,
                 decompose_kv_rank=8, dkv_tail=4)
    rng = np.random.RandomState(0)
    eng.submit(Request(uid=0, prompt=rng.randint(0, cfg.vocab, 12,
                                                 dtype=np.int32),
                       max_new_tokens=12))
    eng.step()                                    # admit: rank-8 factors
    assert eng.cache["k_u"].shape[-1] == 8
    # heterogeneous splice: widen slot 1's factors to rank 12 directly
    import jax.numpy as jnp
    t = eng.cache["k_u"].shape[2]
    wide = {
        "k_u": jnp.ones(eng.cache["k_u"].shape[:-1] + (12,)) * 0.01,
        "v_u": jnp.ones(eng.cache["v_u"].shape[:-1] + (12,)) * 0.01,
        "k_vt": jnp.ones(eng.cache["k_vt"].shape[:-2] + (12,) +
                         eng.cache["k_vt"].shape[-1:]) * 0.01,
        "v_vt": jnp.ones(eng.cache["v_vt"].shape[:-2] + (12,) +
                         eng.cache["v_vt"].shape[-1:]) * 0.01,
        "tail": {k: jnp.zeros_like(v) for k, v in eng.cache["tail"].items()},
    }
    eng.cache = DK.splice_dkv(eng.cache, wide, np.array([1]), np.array([1]))
    assert eng.cache["k_u"].shape[-1] == 12       # splice padded both sides
    eng.rank_eff[1] = 12
    eng.live[1] = Request(uid=99, prompt=np.arange(4, dtype=np.int32),
                          max_new_tokens=2)
    eng.pos[1] = t
    eng.frozen_len[1] = t
    done = eng.run()
    assert sorted(r.uid for r in done) == [0, 99]
    assert eng.stats.tail_folds > 0
    # the wide occupant drained and folds retruncated: width is back to
    # the configured kv_rank (the old max(r_in, r_fold) kept 12 forever)
    assert eng.cache["k_u"].shape[-1] == 8
    assert eng.cache["k_vt"].shape[-2] == 8


def test_compress_tail_uniform_retruncates_to_rank():
    """Unit twin of the ratchet regression: uniform-mode compress_tail on
    factors wider than the configured rank comes back at exactly rank."""
    from repro.models import decomposed_kv as DK
    cfg = all_archs()["deepseek-7b"].reduced()
    kvw = cfg.num_kv_heads * cfg.resolved_head_dim
    nl, b, t, tl, r_in, rank = cfg.num_layers, 2, 12, 4, 12, 8
    rng = np.random.RandomState(1)
    cache = {
        "k_u": rng.randn(nl, b, t, r_in).astype(np.float32),
        "k_vt": rng.randn(nl, b, r_in, kvw).astype(np.float32),
        "v_u": rng.randn(nl, b, t, r_in).astype(np.float32),
        "v_vt": rng.randn(nl, b, r_in, kvw).astype(np.float32),
        "tail": {"k": rng.randn(nl, b, tl, cfg.num_kv_heads,
                                cfg.resolved_head_dim).astype(np.float32),
                 "v": rng.randn(nl, b, tl, cfg.num_kv_heads,
                                cfg.resolved_head_dim).astype(np.float32)},
    }
    out = DK.compress_tail(cache, cfg, rank)
    assert out["k_u"].shape[-1] == rank           # was max(r_in, r_fold)=12
    assert out["k_vt"].shape[-2] == rank
    assert out["k_u"].shape[2] == t + tl


def test_scheduler_buckets_on_cost_hook():
    """The scheduler buckets on the injected COST function, not raw
    prompt length: a +10 modality constant (deliberately not a bucket
    multiple) moves requests across bucket boundaries and regroups the
    admission batches."""
    from repro.serving import Scheduler
    lens = (4, 8, 20, 24)
    reqs = [Request(uid=i, prompt=np.zeros(n, np.int32))
            for i, n in enumerate(lens)]
    plain = Scheduler(bucket=16)
    cost = Scheduler(bucket=16, cost=lambda r: len(r.prompt) + 10)
    for s in (plain, cost):
        for r in reqs:
            s.submit(r)
    # length-based: {4, 8} share bucket 16, {20, 24} share bucket 32
    assert [r.uid for r in plain.next_batch(4)] == [0, 1]
    assert [r.uid for r in plain.next_batch(4)] == [2, 3]
    # cost-based: 14 → 16 | 18, 30 → 32 | 34 → 48
    assert [r.uid for r in cost.next_batch(4)] == [0]
    assert [r.uid for r in cost.next_batch(4)] == [1, 2]
    assert [r.uid for r in cost.next_batch(4)] == [3]
    assert not len(cost)


def test_engine_buckets_on_family_prefill_cost():
    """The engine's scheduler uses the FAMILY-reported prefill cost: a
    VLM prompt costs its token length plus the image-embed rows that
    join the prefill batch, so two prompts whose lengths share a bucket
    land in different buckets once the modality constant is added."""
    cfg = all_archs()["llama-3.2-vision-11b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, slots=2, max_len=96)
    req = Request(uid=0, prompt=np.zeros(7, np.int32))
    assert cfg.num_image_tokens == 16
    assert eng.family.prefill_cost(req) == 7 + 16
    assert eng.sched.cost(req) == eng.family.prefill_cost(req)
    # 7 and 15 share bucket 16 by length, but 23 vs 31: with the image
    # rows both still bucket 32 — push one across: 7+16=23→32, 20+16=36→48
    b = eng.sched.bucket_of
    assert b(eng.sched.cost(Request(uid=1, prompt=np.zeros(7, np.int32)))) \
        != b(eng.sched.cost(Request(uid=2, prompt=np.zeros(20, np.int32))))


@pytest.mark.parametrize("mode", ["slab", "paged", "gang"])
def test_prefill_token_counters_count_the_launched_matrix(mode):
    """Prompts of 5, 9 and 12 tokens at bucket 16 in one admission: a
    power-of-two batch of 4 rows of 16, so 26 prompt tokens and 38 of
    padding, on every path that builds the matrix."""
    from repro.engine import DecomposeEngine, EngineConfig
    cfg = all_archs()["deepseek-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    de = DecomposeEngine(EngineConfig(kv_rank=4, kv_tail=8, sched_bucket=16,
                                      kv_page=8))
    eng = Engine(cfg, params, slots=4, max_len=48, decompose_engine=de,
                 paged=mode == "paged",
                 admission="gang" if mode == "gang" else "per_slot")
    rng = np.random.RandomState(0)
    for i, n in enumerate((5, 9, 12)):
        eng.submit(Request(uid=i, prompt=rng.randint(1, cfg.vocab, n,
                                                     dtype=np.int32),
                           max_new_tokens=2))
    eng.run()
    assert eng.stats.prefill_batches == 1
    got = {m.labels["kind"]: m.value for m in eng.obs.registry.metrics()
           if m.name == "serving_prefill_tokens_total"}
    assert got == {"prompt": 26, "pad": 38}


def test_block_itl_counts_host_time_between_launches(monkeypatch):
    """Under block decode a token's ITL is the time since its slot's
    previous token over the block's steps, so host time between launches
    counts: each request's ITL samples add up to its first-to-last token
    span, slowed host included."""
    import time
    cfg = all_archs()["deepseek-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, slots=2, max_len=64, decompose_kv_rank=4,
                 dkv_tail=16, decode_block=4)
    slow = 0.05
    fold = eng.family.maybe_fold

    def slowed():                    # the host, between two launches
        time.sleep(slow)
        fold()

    monkeypatch.setattr(eng.family, "maybe_fold", slowed)
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i, prompt=rng.randint(1, cfg.vocab, 8,
                                              dtype=np.int32),
                    max_new_tokens=9) for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    s = eng.stats
    assert len(s.itl_s) == s.tokens_out == 2 * 8
    assert s.itl_s.hist.sum == pytest.approx(
        sum(r.t_last - r.t_first for r in reqs))
    # two blocks of 4 per request, each behind one slowed boundary
    assert s.itl_s.hist.sum >= 2 * 2 * slow
