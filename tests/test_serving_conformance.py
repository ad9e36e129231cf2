"""Token-level differential conformance: decomposed-KV serving vs dense.

The paper's serving claim is only checkable end-to-end (Moar et al.,
arXiv:2405.06626): greedy-sampled tokens from the low-rank KV engine must
match the dense-cache engine on the same prompts.  At near-full rank with
``dkv_exact`` (direct SVD, §2.3) every factorization and every per-slot
tail fold is mathematically exact, so the match is TOKEN-EXACT — across
tail-fold boundaries, staggered admissions, and ``slots > len(queue)``.

Also here: splice-admission conformance for a non-dense family (MoE) —
admitting while another slot is live must not perturb the live sequence's
tokens — and the §2.3 parity of ``decompose_kv(exact=True)`` vs Lanczos
at near-full rank.

Mesh-parallel conformance: serving on an 8-host-device (8, 1) mesh —
caches DP-sharded over the slot axis, factorization DP-sharded over
layers×batch — must produce BYTE-IDENTICAL greedy tokens to the 1-device
engine, across tail-fold boundaries and staggered admissions.  The
8-device twin runs in a subprocess (the device count locks at jax init;
tier-1 must keep seeing 1 device).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_archs
from repro.models import model_fns
from repro.serving import Engine, Request

RANK, TAIL, MAX_LEN, MAX_NEW = 64, 4, 64, 12
PROMPT_LENS = (12, 7, 15)


@pytest.fixture(scope="module")
def dense_model():
    cfg = all_archs()["deepseek-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lens=PROMPT_LENS, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab, n, dtype=np.int32) for n in lens]


def _serve(cfg, params, prompts, *, dkv: bool, stagger: bool, slots: int):
    kw = dict(decompose_kv_rank=RANK, dkv_tail=TAIL, dkv_exact=True) \
        if dkv else {}
    eng = Engine(cfg, params, slots=slots, max_len=MAX_LEN, **kw)
    done = []
    if not stagger:
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=MAX_NEW))
        done = eng.run()
    else:
        # arrivals land while earlier requests are mid-decode
        eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=MAX_NEW))
        arrivals = {3 * i: i for i in range(1, len(prompts))}
        for step in range(200):
            if step in arrivals:
                i = arrivals[step]
                eng.submit(Request(uid=i, prompt=prompts[i],
                                   max_new_tokens=MAX_NEW))
            done.extend(eng.step())
            if len(done) == len(prompts) and not any(eng.live):
                break
    assert sorted(r.uid for r in done) == list(range(len(prompts)))
    return {r.uid: r.out_tokens for r in done}, eng.stats


@pytest.mark.parametrize("stagger,slots", [(False, 2), (True, 2), (True, 4)])
def test_dkv_matches_dense_token_level(dense_model, stagger, slots):
    """Greedy tokens of decomposed-KV serving == dense serving, across
    per-slot tail folds; slots=4 also covers slots > len(queue)."""
    cfg, params = dense_model
    prompts = _prompts(cfg)
    dense, _ = _serve(cfg, params, prompts, dkv=False, stagger=stagger,
                      slots=slots)
    dkv, st = _serve(cfg, params, prompts, dkv=True, stagger=stagger,
                     slots=slots)
    assert st.tail_folds > 0             # fold boundaries were crossed
    if stagger:
        assert st.prefill_batches >= 2   # admissions landed while live
    for uid in dense:
        assert dkv[uid] == dense[uid], \
            f"req {uid} diverged: {dkv[uid]} vs {dense[uid]}"


def test_dkv_admits_while_live_without_gang(dense_model):
    """The gang restriction is gone: a second request is admitted while
    slot 0 is mid-decode, and the live request's tokens are bit-identical
    to a solo run."""
    cfg, params = dense_model
    prompts = _prompts(cfg)
    solo, _ = _serve(cfg, params, prompts[:1], dkv=True, stagger=False,
                     slots=2)
    mixed, st = _serve(cfg, params, prompts[:2], dkv=True, stagger=True,
                       slots=2)
    assert st.prefill_batches == 2       # second admission was its own batch
    assert mixed[0] == solo[0], "live dkv sequence corrupted by admission"


def test_moe_splice_admission_token_level():
    """Non-dense family: MoE admits a request while another slot is live;
    the live request's tokens match a solo run token-for-token."""
    cfg = all_archs()["olmoe-1b-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg, lens=(8, 6))
    solo, _ = _serve(cfg, params, prompts[:1], dkv=False, stagger=False,
                     slots=2)
    mixed, st = _serve(cfg, params, prompts, dkv=False, stagger=True,
                       slots=2)
    assert st.prefill_batches == 2       # admitted while slot 0 was live
    assert mixed[0] == solo[0], "live MoE sequence corrupted by admission"


# ---------------------------------------------------------------------------
# Mesh-parallel serving conformance (tentpole)
# ---------------------------------------------------------------------------

DKV_RANK, DKV_TAIL, MESH_SLOTS, MESH_NEW = 8, 4, 8, 12
MESH_PROMPT_LENS = (12, 7, 15)


def _serve_dkv_staggered(cfg, params, prompts, *, mesh, slots=MESH_SLOTS,
                         paged=False):
    """Staggered arrivals (admissions land mid-decode) on the dkv engine,
    rank well below full so tail folds are REAL retruncations."""
    from repro.engine import DecomposeEngine, EngineConfig
    de = DecomposeEngine(EngineConfig(kv_rank=DKV_RANK, kv_tail=DKV_TAIL,
                                      kv_page=4, mesh=mesh))
    eng = Engine(cfg, params, slots=slots, max_len=MAX_LEN,
                 decompose_kv_rank=DKV_RANK, dkv_tail=DKV_TAIL,
                 decompose_engine=de, paged=paged)
    done = []
    eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=MESH_NEW))
    arrivals = {3 * i: i for i in range(1, len(prompts))}
    for step in range(200):
        if step in arrivals:
            i = arrivals[step]
            eng.submit(Request(uid=i, prompt=prompts[i],
                               max_new_tokens=MESH_NEW))
        done.extend(eng.step())
        if len(done) == len(prompts) and not any(eng.live):
            break
    assert eng.stats.tail_folds > 0          # fold boundaries were crossed
    assert eng.stats.prefill_batches >= 2    # admissions landed while live
    return {r.uid: r.out_tokens for r in done}, eng


_SHARDED_SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    sys.path.insert(0, os.path.dirname(os.path.abspath(sys.argv[2])))
    from test_serving_conformance import (MESH_PROMPT_LENS,
                                          _serve_dkv_staggered)
    from repro.configs import all_archs
    from repro.launch.mesh import make_host_mesh
    from repro.models import model_fns

    assert len(jax.devices()) == 8
    cfg = all_archs()["deepseek-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, n, dtype=np.int32)
               for n in MESH_PROMPT_LENS]
    mesh = make_host_mesh(8, 1)
    toks, eng = _serve_dkv_staggered(cfg, params, prompts, mesh=mesh)
    ptoks, peng = _serve_dkv_staggered(cfg, params, prompts, mesh=mesh,
                                       paged=True)
    ku = eng.cache["k_u"]
    json.dump({"tokens": {str(u): t for u, t in toks.items()},
               "paged_tokens": {str(u): t for u, t in ptoks.items()},
               "ku_nshards": len(ku.addressable_shards),
               "ku_spec": str(ku.sharding.spec),
               "paged_free": peng.pager.alloc.free_pages,
               "paged_total": peng.pager.num_pages - 1},
              open(sys.argv[1], "w"))
""")


def test_sharded_serving_byte_identical_to_1_device(dense_model, tmp_path):
    """THE mesh-serving conformance gate: greedy tokens from the 8-host-
    device DP-sharded engine (subprocess — device count locks at jax init)
    are byte-identical to this process's 1-device engine on the same
    staggered schedule, and the live cache really was 8-way sharded."""
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    local, _ = _serve_dkv_staggered(cfg, params, prompts, mesh=None)

    out = tmp_path / "sharded.json"
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)           # the script forces its own 8
    subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT, str(out),
         os.path.abspath(__file__)],
        check=True, env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    got = json.load(open(out))
    assert got["ku_nshards"] == 8        # slot axis genuinely 8-way DP
    assert "data" in got["ku_spec"]
    assert {int(k): v for k, v in got["tokens"].items()} == local, \
        f"sharded tokens diverged: {got['tokens']} vs {local}"
    # the 8-device PAGED twin matches too (and returned every page)
    assert {int(k): v for k, v in got["paged_tokens"].items()} == local, \
        f"sharded PAGED tokens diverged: {got['paged_tokens']} vs {local}"
    assert got["paged_free"] == got["paged_total"], "leaked pages on mesh"


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 devices (CI distributed job forces "
                           "XLA_FLAGS=--xla_force_host_platform_device_"
                           "count=8)")
def test_sharded_serving_inprocess_8dev(dense_model):
    """In-process twin of the subprocess gate for the CI distributed job:
    same schedule, sharded vs unsharded engines in ONE process, plus the
    batched-admission case (all 8 slots admitted at once ⇒ the Lanczos
    factorization batch itself DP-shards)."""
    from repro.launch.mesh import make_host_mesh
    cfg, params = dense_model
    mesh = make_host_mesh(8, 1)
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    a, _ = _serve_dkv_staggered(cfg, params, prompts, mesh=None)
    b, eng = _serve_dkv_staggered(cfg, params, prompts, mesh=mesh)
    assert a == b
    assert len(eng.cache["k_u"].addressable_shards) == 8
    # batched admission: one prefill of 8 × 12-token prompts
    many = _prompts(cfg, lens=(12,) * MESH_SLOTS, seed=1)

    def gang_all(mesh):
        from repro.engine import DecomposeEngine, EngineConfig
        de = DecomposeEngine(EngineConfig(kv_rank=DKV_RANK, kv_tail=DKV_TAIL,
                                          mesh=mesh))
        eng = Engine(cfg, params, slots=MESH_SLOTS, max_len=MAX_LEN,
                     decompose_kv_rank=DKV_RANK, dkv_tail=DKV_TAIL,
                     decompose_engine=de)
        for i, p in enumerate(many):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=MESH_NEW))
        return {r.uid: r.out_tokens for r in eng.run()}

    assert gang_all(None) == gang_all(mesh)


# ---------------------------------------------------------------------------
# Paged-cache conformance (paged engine vs slot engine, prefix cache)
# ---------------------------------------------------------------------------


def test_paged_matches_slot_engine_staggered(dense_model):
    """THE paged gate: block-table serving is greedy-token-EXACT vs the
    slot engine at equal kv_rank (rank 8 — folds are real retruncations),
    across tail-fold boundaries and staggered mid-decode admissions.  The
    paged engine replays the slab arithmetic bit-for-bit (gathers slice
    to the mirrored slab geometry), so this holds at ANY rank, not just
    the near-full exact regime."""
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    slot, _ = _serve_dkv_staggered(cfg, params, prompts, mesh=None,
                                   slots=2)
    paged, eng = _serve_dkv_staggered(cfg, params, prompts, mesh=None,
                                      slots=2, paged=True)
    assert eng.stats.tail_folds > 0
    assert paged == slot, f"paged diverged: {paged} vs {slot}"
    # every page returned to the pool after the queue drained
    assert eng.pager.alloc.free_pages == eng.pager.num_pages - 1
    assert eng.pager.talloc.free_pages == eng.pager.num_tail_pages - 1


def test_paged_matches_slot_engine_batched(dense_model):
    """Full-batch admission twin (all slots admitted in one prefill) plus
    slots > len(queue): the pow2 prefill padding and page write path must
    not perturb tokens."""
    cfg, params = dense_model
    prompts = _prompts(cfg)

    def serve(paged):
        from repro.engine import DecomposeEngine, EngineConfig
        de = DecomposeEngine(EngineConfig(kv_rank=DKV_RANK,
                                          kv_tail=DKV_TAIL, kv_page=4))
        eng = Engine(cfg, params, slots=4, max_len=MAX_LEN,
                     decompose_kv_rank=DKV_RANK, dkv_tail=DKV_TAIL,
                     decompose_engine=de, paged=paged)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=MAX_NEW))
        return {r.uid: r.out_tokens for r in eng.run()}, eng

    slot, _ = serve(False)
    paged, eng = serve(True)
    assert eng.stats.tail_folds > 0
    assert paged == slot
    assert eng.pager.alloc.free_pages == eng.pager.num_pages - 1


def test_paged_matches_slot_mixed_page_counts(dense_model):
    """Regression: staggered admissions from DIFFERENT plen buckets give
    the slots different block-table widths, so decode/fold gathers read
    the id-0 sink page through the block-table padding.  A fold must
    never leave residue in the sink (non-folding slots' rows scatter as
    zeros) or the shorter slot's next fold retruncates garbage and its
    tokens drift off the slot engine's."""
    cfg, params = dense_model

    def serve(paged):
        from repro.engine import DecomposeEngine, EngineConfig
        de = DecomposeEngine(EngineConfig(kv_rank=DKV_RANK,
                                          kv_tail=DKV_TAIL, kv_page=4))
        eng = Engine(cfg, params, slots=2, max_len=MAX_LEN,
                     decompose_kv_rank=DKV_RANK, dkv_tail=DKV_TAIL,
                     decompose_engine=de, paged=paged)
        rng = np.random.RandomState(7)
        # bucket 16 vs bucket 32 → 4 vs 8 pages per slot
        eng.submit(Request(uid=0, prompt=rng.randint(0, cfg.vocab, 12,
                                                     dtype=np.int32),
                           max_new_tokens=20))
        done = []
        for step in range(200):
            if step == 3:
                eng.submit(Request(uid=1,
                                   prompt=rng.randint(0, cfg.vocab, 20,
                                                      dtype=np.int32),
                                   max_new_tokens=8))
            done.extend(eng.step())
            if len(done) == 2 and not any(eng.live):
                break
        assert eng.stats.tail_folds >= 2
        return {r.uid: r.out_tokens for r in done}

    slot = serve(False)
    paged = serve(True)
    assert paged == slot, f"sink-page residue corrupted decode: " \
                          f"{paged} vs {slot}"


def test_prefix_cache_never_matches_padding_only(dense_model):
    """Regression: two UNRELATED short prompts share only their bucket
    left-padding (12 zero rows at bucket 16).  A boundary lying entirely
    inside the pad region must not count as a shared prefix — the cached
    low-rank basis was fit to the OTHER prompt's real rows — so the
    lookup must miss and tokens must match the prefix-cache-off engine."""
    cfg, params = dense_model
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab, 4, dtype=np.int32)
               for _ in range(2)]
    assert not np.array_equal(prompts[0], prompts[1])

    def serve(prefix_cap):
        from repro.engine import DecomposeEngine, EngineConfig
        de = DecomposeEngine(EngineConfig(kv_rank=8, kv_tail=16, kv_page=4,
                                          kv_prefix_cache=prefix_cap))
        eng = Engine(cfg, params, slots=1, max_len=MAX_LEN,
                     decompose_kv_rank=8, dkv_tail=16,
                     decompose_engine=de, paged=True)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=6))
        done = eng.run()
        return {r.uid: r.out_tokens for r in done}, eng

    off, _ = serve(0)
    on, eng = serve(4)
    assert eng.stats.prefix_hits == 0, \
        "padding-only boundary must not match unrelated prompts"
    assert on == off


def test_paged_prefix_cache_hit_miss_evict(dense_model):
    """Prefix-cache conformance: a shared-system-prompt workload admits
    later requests as HITS (refcounted page splice + tail-only suffix
    prefill — no prefix forward, no Lanczos) with greedy tokens matching
    the prefix-cache-off engine at near-full exact rank; capacity-1
    forces LRU eviction; no pages leak after the queue drains.

    (Hit and miss keep the suffix rows on different sides of the
    factorization — both exact vs dense to ~1e-6 — so greedy near-ties
    CAN flip; the fixed seed below is verified tie-free, like the other
    exact-rank suites in this file.)"""
    cfg, params = dense_model
    rng = np.random.RandomState(1)
    sys_prompt = rng.randint(0, cfg.vocab, 12, dtype=np.int32)
    prompts = [np.concatenate([sys_prompt,
                               rng.randint(0, cfg.vocab, 3, dtype=np.int32)])
               for _ in range(4)]

    def serve(prefix_cap):
        from repro.engine import DecomposeEngine, EngineConfig
        de = DecomposeEngine(EngineConfig(
            kv_rank=48, kv_tail=8, kv_page=4, kv_exact=True,
            kv_prefix_cache=prefix_cap))
        eng = Engine(cfg, params, slots=2, max_len=MAX_LEN,
                     decompose_kv_rank=48, dkv_tail=8, dkv_exact=True,
                     decompose_engine=de, paged=True)
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=8))
        done = eng.run()
        return {r.uid: r.out_tokens for r in done}, eng

    off, _ = serve(0)
    on, eng = serve(8)
    assert eng.stats.prefix_hits >= 2            # later arrivals hit
    assert eng.stats.prefix_misses >= 1          # first arrival missed
    assert on == off, f"prefix-cache hits diverged: {on} vs {off}"
    # cached pages outlive their slots (entries hold refs, slots drained)
    assert len(eng.pager.prefix) >= 1
    assert any(rc >= 1 for rc in eng.pager.alloc.live_refs.values())
    used = eng.pager.num_pages - 1 - eng.pager.alloc.free_pages
    assert used == sum(len(e.pages)
                       for e in eng.pager.prefix._entries.values())
    eng.pager.prefix.drop_all()                  # release the cache's refs
    assert eng.pager.alloc.free_pages == eng.pager.num_pages - 1

    # capacity-1: the second distinct prompt evicts the first (LRU)
    evict, eng1 = serve(1)
    assert eng1.pager.prefix.evictions >= 1
    assert len(eng1.pager.prefix) == 1
    assert evict == off                          # eviction never corrupts


def test_paged_prefix_hit_skips_prefill_work(dense_model):
    """A full-page hit admits with tail-only work: the hit admission runs
    NO decomposition (stats show a hit, and the slot's frozen factors are
    the cached entry's pages — refcount 2 while both referents live)."""
    cfg, params = dense_model
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, cfg.vocab, 15, dtype=np.int32)

    from repro.engine import DecomposeEngine, EngineConfig
    de = DecomposeEngine(EngineConfig(kv_rank=48, kv_tail=8, kv_page=4,
                                      kv_exact=True, kv_prefix_cache=4))
    eng = Engine(cfg, params, slots=2, max_len=MAX_LEN,
                 decompose_kv_rank=48, dkv_tail=8, dkv_exact=True,
                 decompose_engine=de, paged=True)
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=4))
    eng.run()
    assert eng.stats.prefix_misses == 1
    eng.submit(Request(uid=1, prompt=prompt.copy(), max_new_tokens=4))
    eng.step()                                   # admission lands
    assert eng.stats.prefix_hits == 1
    slot = next(i for i, r in enumerate(eng.live) if r is not None)
    shared = eng.pager.bt_u[slot]
    refs = eng.pager.alloc.live_refs
    assert shared and all(refs[p] >= 2 for p in shared), \
        "hit slot must alias the cached entry's pages, not copy them"
    eng.run()
    # copy-on-write: if the slot folded, the shared pages are untouched
    assert all(p in refs or p in eng.pager.alloc.live_refs
               for p in shared)


def test_paged_hit_survives_same_batch_eviction(dense_model):
    """Regression: one admission batch carrying a HIT on the LRU entry
    plus a MISS whose insertion evicts that entry (capacity 1).  The hit
    takes its page refs BEFORE the miss inserts, so eviction only drops
    the cache's refs — the hit slot keeps valid pages and the engine
    neither crashes nor leaks."""
    cfg, params = dense_model
    rng = np.random.RandomState(1)
    p1 = rng.randint(0, cfg.vocab, 15, dtype=np.int32)
    p2 = rng.randint(0, cfg.vocab, 15, dtype=np.int32)

    from repro.engine import DecomposeEngine, EngineConfig
    de = DecomposeEngine(EngineConfig(kv_rank=48, kv_tail=8, kv_page=4,
                                      kv_exact=True, kv_prefix_cache=1))
    eng = Engine(cfg, params, slots=2, max_len=MAX_LEN,
                 decompose_kv_rank=48, dkv_tail=8, dkv_exact=True,
                 decompose_engine=de, paged=True)
    eng.submit(Request(uid=0, prompt=p1, max_new_tokens=4))
    eng.run()                                    # populates the cache
    # one batch: hit on p1's entry + miss that evicts it (capacity 1)
    eng.submit(Request(uid=1, prompt=p1.copy(), max_new_tokens=6))
    eng.submit(Request(uid=2, prompt=p2, max_new_tokens=6))
    done = {r.uid: r for r in eng.run()}
    assert eng.stats.prefix_hits == 1
    assert eng.pager.prefix.evictions >= 1
    assert len(done[1].out_tokens) == 6 and len(done[2].out_tokens) == 6
    eng.pager.prefix.drop_all()
    assert eng.pager.alloc.free_pages == eng.pager.num_pages - 1


# ---------------------------------------------------------------------------
# Fused decode-loop conformance (single-dispatch multi-token blocks)
# ---------------------------------------------------------------------------

FUSED_BLOCKS = (2, 3, 8, 32)


def _serve_fused(cfg, params, prompts, *, block, paged=False, slots=2,
                 eos_id=None, mesh=None, dkv=True, max_new=MESH_NEW):
    """All prompts submitted up front with slots < len(prompts): later
    requests are admitted organically as earlier ones finish, so block
    boundaries, folds, and admission rounds all interleave."""
    from repro.engine import DecomposeEngine, EngineConfig
    kw = {}
    if dkv:
        de = DecomposeEngine(EngineConfig(kv_rank=DKV_RANK, kv_tail=DKV_TAIL,
                                          kv_page=4, decode_block=block,
                                          mesh=mesh))
        kw = dict(decompose_kv_rank=DKV_RANK, dkv_tail=DKV_TAIL,
                  decompose_engine=de, paged=paged)
    eng = Engine(cfg, params, slots=slots, max_len=MAX_LEN,
                 eos_id=eos_id, **kw, **({} if dkv
                                         else {"decode_block": block}))
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
    done = eng.run()
    assert sorted(r.uid for r in done) == list(range(len(prompts)))
    return {r.uid: r.out_tokens for r in done}, eng


@pytest.mark.parametrize("paged", [False, True])
def test_fused_decode_token_exact(dense_model, paged):
    """THE fused gate: every block length produces byte-identical tokens
    to the single-step engine, across tail-fold boundaries and organic
    staggered admissions (slots < requests), slot AND paged."""
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    base, e1 = _serve_fused(cfg, params, prompts, block=1, paged=paged)
    assert e1.stats.tail_folds > 0           # folds were crossed
    assert e1.stats.blocks == e1.stats.decode_steps
    for blk in FUSED_BLOCKS:
        got, eb = _serve_fused(cfg, params, prompts, block=blk, paged=paged)
        assert got == base, f"block={blk} diverged: {got} vs {base}"
        assert eb.stats.decode_steps == e1.stats.decode_steps
        assert eb.stats.blocks < e1.stats.blocks, \
            "fused run should launch fewer blocks than rounds"
        assert eb.stats.tail_folds == e1.stats.tail_folds
    if paged:                                # no page leaks under fusion
        assert eb.pager.alloc.free_pages == eb.pager.num_pages - 1
        assert eb.pager.talloc.free_pages == eb.pager.num_tail_pages - 1


@pytest.mark.parametrize("paged", [False, True])
def test_fused_decode_eos_mid_block(dense_model, paged):
    """A stop token sampled mid-block ends the block early ON DEVICE, so
    the request finishes at the same round (and with the same tokens) as
    the single-step engine — no overshoot past EOS."""
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    probe, _ = _serve_fused(cfg, params, prompts, block=1, paged=paged)
    # pin an eos that the greedy stream REALLY emits, mid-sequence, so
    # both engines must cut that request short at the same position
    eos = probe[0][len(probe[0]) // 2]
    base, e1 = _serve_fused(cfg, params, prompts, block=1, paged=paged,
                            eos_id=eos)
    assert e1.stats.stopped_eos >= 1
    assert len(base[0]) < len(probe[0])      # it actually cut short
    for blk in FUSED_BLOCKS:
        got, eb = _serve_fused(cfg, params, prompts, block=blk, paged=paged,
                               eos_id=eos)
        assert got == base, f"block={blk} with eos diverged"
        assert eb.stats.stopped_eos == e1.stats.stopped_eos
        assert eb.stats.decode_steps == e1.stats.decode_steps


def test_fused_decode_dense_family(dense_model):
    """The dense (non-decomposed) cache path through the fused loop:
    budget horizons only, no folds."""
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    base, _ = _serve_fused(cfg, params, prompts, block=1, dkv=False)
    for blk in (4, 32):
        got, eb = _serve_fused(cfg, params, prompts, block=blk, dkv=False)
        assert got == base, f"dense block={blk} diverged"
        assert eb.stats.blocks < eb.stats.decode_steps


def test_fused_itl_and_blocks_accounting(dense_model):
    """Satellite: under block decode every emitted token gets one ITL
    sample (wall/steps per token of its block), tokens_out is exact, and
    the blocks counter counts LAUNCHES, not rounds."""
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    _, eng = _serve_fused(cfg, params, prompts, block=8)
    s = eng.stats
    assert s.blocks < s.decode_steps
    assert len(s.itl_s) == s.tokens_out      # one ITL sample per decode tok
    assert all(dt >= 0 for dt in s.itl_s)
    # each request's first token comes from admission (counted as TTFT),
    # the other max_new − 1 from decode rounds
    assert s.tokens_out == sum(MESH_NEW - 1 for _ in prompts)


_FUSED_SHARDED_SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    sys.path.insert(0, os.path.dirname(os.path.abspath(sys.argv[2])))
    from test_serving_conformance import MESH_PROMPT_LENS, _serve_fused
    from repro.configs import all_archs
    from repro.launch.mesh import make_host_mesh
    from repro.models import model_fns

    assert len(jax.devices()) == 8
    cfg = all_archs()["deepseek-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, n, dtype=np.int32)
               for n in MESH_PROMPT_LENS]
    mesh = make_host_mesh(8, 1)
    out = {}
    for paged in (False, True):
        toks, eng = _serve_fused(cfg, params, prompts, block=4,
                                 paged=paged, slots=8, mesh=mesh)
        key = "paged" if paged else "slot"
        out[key] = {str(u): t for u, t in toks.items()}
        out[key + "_blocks"] = eng.stats.blocks
        out[key + "_steps"] = eng.stats.decode_steps
        if not paged:
            out["ku_nshards"] = len(eng.cache["k_u"].addressable_shards)
    json.dump(out, open(sys.argv[1], "w"))
""")


def test_fused_sharded_byte_identical_to_1_device(dense_model, tmp_path):
    """8-device fused twin: block-4 fused decode on the (8, 1) mesh
    (subprocess) is byte-identical to this process's 1-device SINGLE-STEP
    engine — fusion and sharding compose without perturbing tokens."""
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    local, _ = _serve_fused(cfg, params, prompts, block=1, slots=8)

    out = tmp_path / "fused_sharded.json"
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    subprocess.run(
        [sys.executable, "-c", _FUSED_SHARDED_SCRIPT, str(out),
         os.path.abspath(__file__)],
        check=True, env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    got = json.load(open(out))
    assert got["ku_nshards"] == 8
    for key in ("slot", "paged"):
        assert {int(k): v for k, v in got[key].items()} == local, \
            f"8-device fused {key} tokens diverged"
        assert got[key + "_blocks"] < got[key + "_steps"]


# ---------------------------------------------------------------------------
# Async prefill/decode conformance (disaggregated admissions, DESIGN.md §12)
# ---------------------------------------------------------------------------


def _serve_async_det(cfg, params, prompts, *, mesh=None, block=1,
                     paged=False, sync=False, slots=MESH_SLOTS, obs=None):
    """Staggered mid-decode arrivals on the async-dispatch engine in
    DETERMINISTIC ready-order (tickets splice at their dispatch round),
    or the synchronous engine when ``sync=True`` — identical schedule,
    so the tokens must be byte-identical."""
    from repro.engine import DecomposeEngine, EngineConfig
    de = DecomposeEngine(EngineConfig(kv_rank=DKV_RANK, kv_tail=DKV_TAIL,
                                      kv_page=4, decode_block=block,
                                      mesh=mesh))
    akw = {} if sync else dict(prefill_async=True,
                               ready_order="deterministic")
    eng = Engine(cfg, params, slots=slots, max_len=MAX_LEN,
                 decompose_kv_rank=DKV_RANK, dkv_tail=DKV_TAIL,
                 decompose_engine=de, paged=paged, obs=obs, **akw)
    done = []
    eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=MESH_NEW))
    arrivals = {3 * i: i for i in range(1, len(prompts))}
    for step in range(200):
        if step in arrivals:
            i = arrivals[step]
            eng.submit(Request(uid=i, prompt=prompts[i],
                               max_new_tokens=MESH_NEW))
        done.extend(eng.step())
        if len(done) == len(prompts) and not any(eng.live):
            break
    assert sorted(r.uid for r in done) == list(range(len(prompts)))
    return {r.uid: r.out_tokens for r in done}, eng


@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("block", [1, 4])
def test_async_det_conformance_1dev(dense_model, paged, block):
    """THE async gate (1 device): asynchronous admission dispatch in
    deterministic ready-order is token-byte-identical to the synchronous
    engine under staggered mid-decode arrivals — slot and paged,
    single-step and fused decode, across tail-fold boundaries."""
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    base, _ = _serve_async_det(cfg, params, prompts, block=block,
                               paged=paged, sync=True, slots=2)
    det, eng = _serve_async_det(cfg, params, prompts, block=block,
                                paged=paged, slots=2)
    assert eng.stats.tail_folds > 0
    assert det == base, f"async-det diverged (paged={paged}, block={block})"
    if paged:                            # clean drain, every page returned
        assert eng.pager.alloc.free_pages == eng.pager.num_pages - 1
        assert eng.pager.talloc.free_pages == eng.pager.num_tail_pages - 1


_ASYNC_SHARDED_SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    sys.path.insert(0, os.path.dirname(os.path.abspath(sys.argv[2])))
    from test_serving_conformance import (MESH_PROMPT_LENS,
                                          _serve_async_det)
    from repro.configs import all_archs
    from repro.launch.mesh import make_host_mesh
    from repro.models import model_fns

    assert len(jax.devices()) == 8
    cfg = all_archs()["deepseek-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, n, dtype=np.int32)
               for n in MESH_PROMPT_LENS]
    mesh = make_host_mesh(8, 1)
    out = {}
    for key, block, paged in (("slot_b1", 1, False), ("slot_b4", 4, False),
                              ("paged_b1", 1, True), ("paged_b4", 4, True)):
        toks, eng = _serve_async_det(cfg, params, prompts, mesh=mesh,
                                     block=block, paged=paged)
        out[key] = {str(u): t for u, t in toks.items()}
        if key == "slot_b1":
            out["ku_nshards"] = len(eng.cache["k_u"].addressable_shards)
    json.dump(out, open(sys.argv[1], "w"))
""")


def test_async_sharded_byte_identical_to_sync_1dev(dense_model, tmp_path):
    """8-device async twin (subprocess — device count locks at jax init):
    async dispatch in deterministic ready-order on the (8, 1) mesh is
    byte-identical to this process's 1-device SYNCHRONOUS engine at the
    same block size, for every combination of {slot, paged} ×
    {single-step, fused} decode — disaggregation, fusion, and sharding
    compose without perturbing tokens.  (Arrivals land at outer-step
    indices, so a fused run admits them at later rounds and co-folds
    differently: block 4 is compared with sync block 4, and fused vs
    single-step with identical schedules is gated by
    ``test_fused_decode_token_exact``.)"""
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    local = {blk: _serve_async_det(cfg, params, prompts, sync=True,
                                   block=blk)[0] for blk in (1, 4)}

    out = tmp_path / "async_sharded.json"
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)           # the script forces its own 8
    subprocess.run(
        [sys.executable, "-c", _ASYNC_SHARDED_SCRIPT, str(out),
         os.path.abspath(__file__)],
        check=True, env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    got = json.load(open(out))
    assert got["ku_nshards"] == 8        # slot axis genuinely 8-way DP
    for key, blk in (("slot_b1", 1), ("slot_b4", 4), ("paged_b1", 1),
                     ("paged_b4", 4)):
        assert {int(k): v for k, v in got[key].items()} == local[blk], \
            f"8-device async {key} tokens diverged vs 1-device sync"


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 devices (CI distributed job forces "
                           "XLA_FLAGS=--xla_force_host_platform_device_"
                           "count=8)")
def test_async_sharded_inprocess_8dev(dense_model):
    """In-process twin of the async subprocess gate for the CI
    distributed job: sync-1dev-schedule vs async-det on the (8, 1) mesh
    in ONE process, single-step and fused."""
    from repro.launch.mesh import make_host_mesh
    cfg, params = dense_model
    mesh = make_host_mesh(8, 1)
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    for block in (1, 4):
        base, _ = _serve_async_det(cfg, params, prompts, sync=True,
                                   block=block)
        got, eng = _serve_async_det(cfg, params, prompts, mesh=mesh,
                                    block=block)
        assert got == base, f"8-device async block={block} diverged"
    assert len(eng.cache["k_u"].addressable_shards) == 8


def test_exact_svd_vs_lanczos_near_full_rank():
    """§2.3: on a KV-like block (decaying spectrum — real K/V rows are
    strongly correlated), direct SVD (exact=True) and Lanczos agree as
    operators at near-full rank, with the exact path never worse
    (floating-point Lanczos loses trailing directions on FLAT spectra,
    which is exactly why the serving knob exists)."""
    from repro.engine import DecomposeEngine, EngineConfig
    eng = DecomposeEngine(EngineConfig())
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    q1, _ = jnp.linalg.qr(jax.random.normal(k1, (4, 24, 24)))
    q2, _ = jnp.linalg.qr(jnp.swapaxes(
        jax.random.normal(k2, (4, 24, 64)), -1, -2))
    s = jnp.power(0.6, jnp.arange(24))
    x = jnp.einsum("btr,r,bhr->bth", q1, s, q2)      # [4, 24, 64]
    nrm = float(jnp.linalg.norm(x))
    for r in (24, 20):                   # full and near-full row rank
        ue, vte = eng.decompose_kv(x, r, exact=True)
        ul, vtl = eng.decompose_kv(x, r)
        rec_e = jnp.einsum("btr,brh->bth", ue, vte)
        rec_l = jnp.einsum("btr,brh->bth", ul, vtl)
        err_e = float(jnp.linalg.norm(rec_e - x)) / nrm
        err_l = float(jnp.linalg.norm(rec_l - x)) / nrm
        assert err_e <= 1e-3             # direct SVD: (near-)exact
        assert err_e <= err_l + 1e-6     # exact never worse than Lanczos
        np.testing.assert_allclose(np.asarray(rec_l), np.asarray(rec_e),
                                   rtol=1e-3, atol=1e-3)
    # a requested rank beyond min(T, kvw) caps at the achievable rank
    uc, _ = eng.decompose_kv(x, 100, exact=True)
    assert uc.shape[-1] == 24


# ---------------------------------------------------------------------------
# Observability neutrality (DESIGN.md §13: zero device ops)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("sync", [True, False])
@pytest.mark.parametrize("block", [1, 4])
def test_observability_is_token_neutral(dense_model, paged, sync, block):
    """THE §13 gate: full observability — metrics registry AND span
    tracing enabled — must produce byte-identical tokens to the default
    (trace-off) engine, for {slot, paged} × {sync, async} × {single-step,
    fused} decode, across tail folds and staggered mid-decode arrivals.
    Instrumentation is purely host-side; if a span or counter ever feeds
    a jit or reorders a device launch, this is the test that catches it.
    """
    from repro.obs import Observability, validate_trace
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    base, _ = _serve_async_det(cfg, params, prompts, block=block,
                               paged=paged, sync=sync)
    obs = Observability(trace=True)
    got, eng = _serve_async_det(cfg, params, prompts, block=block,
                                paged=paged, sync=sync, obs=obs)
    assert got == base, \
        f"observability perturbed tokens (paged={paged}, sync={sync}, " \
        f"block={block})"
    # the instrumented run really recorded: request-lifecycle spans for
    # every request, and engine stats on the obs registry
    spans = validate_trace(obs.tracer.to_json())
    assert spans >= 4 * len(prompts)     # request/queue/prefill/decode each
    names = {ev["name"] for ev in obs.tracer.events}
    expect = {"request", "queue", "prefill", "decode", "step"}
    if not sync:
        expect |= {"splice", "ticket"}
    assert expect <= names, f"missing spans: {expect - names}"
    reg_names = {m.name for m in obs.registry.metrics()}
    assert "serving_tokens_out" in reg_names
    assert eng.stats.registry is obs.registry


def test_engine_stats_memory_bounded(dense_model):
    """Satellite (a): latency series keep O(1) streaming state + a capped
    reservoir — a long-running engine's stats must not grow with every
    token — while ``len(itl_s) == tokens_out`` still holds via the
    histogram counter."""
    from repro.obs.registry import RESERVOIR_CAP
    cfg, params = dense_model
    prompts = _prompts(cfg, lens=MESH_PROMPT_LENS)
    _, eng = _serve_async_det(cfg, params, prompts, block=1, sync=True)
    s = eng.stats
    assert len(s.itl_s) == s.tokens_out
    assert len(s.ttft_s) == len(s.ttft_queue_s) == len(s.ttft_compute_s)
    for series in (s.itl_s, s.ttft_s):
        assert len(series.hist.recent) <= RESERVOIR_CAP
        assert series.hist.count == len(series)
    # the histogram mean is exact (streaming sum/count, not reservoir)
    assert s.mean_itl_s == pytest.approx(s.itl_s.hist.sum
                                         / s.itl_s.hist.count)
    # simulate a long run: observe far past the cap, memory stays bounded
    h = s.itl_s.hist
    before = len(h.recent)
    for i in range(4 * RESERVOIR_CAP):
        s.itl_s.append(1e-3 * (1 + i % 7))
    assert len(h.recent) == RESERVOIR_CAP
    assert len(s.itl_s) == s.tokens_out + 4 * RESERVOIR_CAP
    assert before <= RESERVOIR_CAP

# ---------------------------------------------------------------------------
# Multi-family serving conformance (ServingFamily protocol, DESIGN.md §15)
# ---------------------------------------------------------------------------

FAMILY_ARCHS = ("mamba2-780m", "olmoe-1b-7b", "zamba2-1.2b")
FAM_PROMPT_LENS = (7, 12, 19, 5)
FAM_NEW = 12

_FAM_MODELS = {}


def _family_model(arch):
    """Reduced cfg + params per family arch, cached across tests.

    MoE pins ``capacity_factor=8.0``: expert capacity is
    ``ceil(tokens · top_k · cf / experts)``, which depends on the BATCH
    token count — a capacity-dropped token routes differently between
    the solo and concurrent runs by design, not by bug.  With the cap
    slack the router is batch-size-invariant and token-exactness is a
    real engine invariant."""
    if arch not in _FAM_MODELS:
        from repro.configs import all_archs as _archs
        cfg = _archs()[arch].reduced()
        if cfg.family == "moe":
            cfg = cfg.replace(capacity_factor=8.0)
        params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
        _FAM_MODELS[arch] = (cfg, params)
    return _FAM_MODELS[arch]


def _serve_family(cfg, params, prompts, *, slots=4, block=1, async_=False,
                  mesh=None, stagger=True, max_new=FAM_NEW):
    """Serve a non-transformer-dkv family on the generic engine:
    staggered mid-decode arrivals (or all-up-front), optional fused
    decode blocks, optional async admission in deterministic order, and
    an optional DP mesh (threaded through the engine config with
    ``decompose_kv_rank=0`` so the family cache path stays on)."""
    kw = {}
    if mesh is not None:
        from repro.engine import DecomposeEngine, EngineConfig
        kw.update(decompose_engine=DecomposeEngine(EngineConfig(mesh=mesh)),
                  decompose_kv_rank=0)
    if block > 1:
        kw["decode_block"] = block
    if async_:
        kw.update(prefill_async=True, ready_order="deterministic")
    eng = Engine(cfg, params, slots=slots, max_len=96, **kw)
    done = []
    if not stagger:
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
        done = eng.run()
    else:
        eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=max_new))
        arrivals = {3 * i: i for i in range(1, len(prompts))}
        for step in range(300):
            if step in arrivals:
                i = arrivals[step]
                eng.submit(Request(uid=i, prompt=prompts[i],
                                   max_new_tokens=max_new))
            done.extend(eng.step())
            if len(done) == len(prompts) and not any(eng.live):
                break
    assert sorted(r.uid for r in done) == list(range(len(prompts)))
    return {r.uid: r.out_tokens for r in done}, eng


def _solo_family(cfg, params, prompts, max_new=FAM_NEW):
    """Reference: each request alone on a fresh single-slot engine — no
    batching, no splice, no shared state."""
    out = {}
    for i, p in enumerate(prompts):
        toks, _ = _serve_family(cfg, params, [p], slots=1, stagger=False,
                                max_new=max_new)
        out[i] = toks[0]
    return out


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_family_staggered_matches_solo(arch):
    """THE multi-family gate: Mamba2 / MoE / hybrid traffic served with
    staggered mid-decode admissions on the generic slot engine produces
    greedy tokens token-EXACT vs each request decoded alone — admission
    splices (conv/ssm state rows, KV rows, router state) never perturb
    a live or later sequence."""
    cfg, params = _family_model(arch)
    prompts = _prompts(cfg, lens=FAM_PROMPT_LENS)
    solo = _solo_family(cfg, params, prompts)
    got, eng = _serve_family(cfg, params, prompts)
    assert eng.stats.prefill_batches >= 2    # admissions landed while live
    for uid in solo:
        assert got[uid] == solo[uid], \
            f"{arch} req {uid} diverged: {got[uid]} vs {solo[uid]}"


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_family_fused_block_matches_single_step(arch):
    """Fused decode blocks are pure execution strategy for EVERY family:
    block-4 serving is byte-identical to single-step, with fewer
    launches covering the same rounds."""
    cfg, params = _family_model(arch)
    prompts = _prompts(cfg, lens=FAM_PROMPT_LENS)
    base, e1 = _serve_family(cfg, params, prompts, block=1)
    got, eb = _serve_family(cfg, params, prompts, block=4)
    assert got == base, f"{arch} fused diverged"
    assert eb.stats.blocks < e1.stats.blocks
    assert eb.stats.tokens_out == e1.stats.tokens_out


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_family_async_det_matches_sync(arch):
    """Async admission dispatch (deterministic ready-order) composes
    with every family: byte-identical to the synchronous engine under
    the same staggered schedule."""
    cfg, params = _family_model(arch)
    prompts = _prompts(cfg, lens=FAM_PROMPT_LENS)
    base, _ = _serve_family(cfg, params, prompts)
    got, eng = _serve_family(cfg, params, prompts, async_=True)
    assert got == base, f"{arch} async-det diverged"
    assert not eng._pool and not eng._reserved.any()


def test_family_fused_async_compose():
    """Fusion AND async admission together on non-transformer families —
    the full feature matrix holds off the dkv path too."""
    for arch in ("mamba2-780m", "olmoe-1b-7b"):
        cfg, params = _family_model(arch)
        prompts = _prompts(cfg, lens=FAM_PROMPT_LENS)
        base, _ = _serve_family(cfg, params, prompts)
        got, _ = _serve_family(cfg, params, prompts, block=4, async_=True)
        assert got == base, f"{arch} fused+async diverged"


_FAMILY_SHARDED_SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    sys.path.insert(0, os.path.dirname(os.path.abspath(sys.argv[2])))
    from test_serving_conformance import (FAM_PROMPT_LENS, _family_model,
                                          _serve_family)
    from repro.launch.mesh import make_host_mesh

    assert len(jax.devices()) == 8
    cfg, params = _family_model("mamba2-780m")
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, n, dtype=np.int32)
               for n in FAM_PROMPT_LENS]
    mesh = make_host_mesh(8, 1)
    toks, eng = _serve_family(cfg, params, prompts, slots=8, mesh=mesh)
    conv = eng.cache["conv"]
    json.dump({"tokens": {str(u): t for u, t in toks.items()},
               "conv_nshards": len(conv.addressable_shards),
               "conv_spec": str(conv.sharding.spec)},
              open(sys.argv[1], "w"))
""")


def test_family_sharded_byte_identical_to_1_device(tmp_path):
    """8-device non-transformer twin (subprocess — device count locks at
    jax init): Mamba2 serving with the conv/ssm state DP-sharded over
    the slot axis on an (8, 1) mesh is byte-identical to this process's
    1-device engine on the same staggered schedule."""
    cfg, params = _family_model("mamba2-780m")
    prompts = _prompts(cfg, lens=FAM_PROMPT_LENS)
    local, _ = _serve_family(cfg, params, prompts, slots=8)

    out = tmp_path / "family_sharded.json"
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)           # the script forces its own 8
    subprocess.run(
        [sys.executable, "-c", _FAMILY_SHARDED_SCRIPT, str(out),
         os.path.abspath(__file__)],
        check=True, env=env, timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    got = json.load(open(out))
    assert got["conv_nshards"] == 8      # slot axis genuinely 8-way DP
    assert "data" in got["conv_spec"]
    assert {int(k): v for k, v in got["tokens"].items()} == local, \
        f"sharded mamba2 tokens diverged: {got['tokens']} vs {local}"


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 devices (CI distributed job forces "
                           "XLA_FLAGS=--xla_force_host_platform_device_"
                           "count=8)")
def test_family_sharded_inprocess_8dev():
    """In-process twin of the mamba2 subprocess gate for the CI
    distributed job: sharded vs unsharded family engines in ONE
    process."""
    from repro.launch.mesh import make_host_mesh
    cfg, params = _family_model("mamba2-780m")
    mesh = make_host_mesh(8, 1)
    prompts = _prompts(cfg, lens=FAM_PROMPT_LENS)
    a, _ = _serve_family(cfg, params, prompts, slots=8)
    b, eng = _serve_family(cfg, params, prompts, slots=8, mesh=mesh)
    assert a == b
    assert len(eng.cache["conv"].addressable_shards) == 8
