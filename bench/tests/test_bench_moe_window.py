"""The window/full expert block (``bench/blocks/moe_window.py``) and its
cell: the weights tree is the engine's, the plain reference agrees with
the program's decomposed-KV prefill and decode at float32 (window rings,
YaRN and the held experts included), the configuration, traffic and cell
load and pass ``run.validate``, the counted work adds up, and
``moe_roofline`` reads a recorded op list."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import run, spec, weights  # noqa: E402
from bench.tests._tiny import run_cell  # noqa: E402

DATA = Path(__file__).resolve().parent / "data_mellum"
TINY = spec.config_file("tiny-mellum", DATA)
CELL_CONF = spec.config_file("mellum2-12b-ep4")
BLOCK = spec.block_module("moe_window")
CATALOG_URL = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
               "blob/main/config.json")


def _cfg(conf, **kw):
    from repro.configs.base import get_arch
    return get_arch(conf["arch"]).replace(**conf.get("replace", {}), **kw)


@pytest.mark.parametrize("conf", [CELL_CONF, TINY],
                         ids=["mellum2-12b-ep4", "tiny"])
def test_weights_tree_is_the_engines(conf):
    from repro.models import api
    want = api.abstract_params(_cfg(conf))
    got = weights.abstract(BLOCK.layout(conf["model"]), conf["dtype"])
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_the_file_holds_the_catalog_config_and_the_program_runs_it():
    """The published keys are in the file as published (but the held
    experts, listed in ``reduced``), and the program's layer kinds, RoPE
    and router read the same numbers."""
    c = CELL_CONF
    cfg = _cfg(c)
    assert c["source"] == CATALOG_URL and c["reduced"] == ["num_experts"]
    assert c["num_experts"] == 16 and c["published"]["num_experts"] == 64
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    assert tuple(kinds[k] for k in c["layer_types"]) == cfg.layer_kinds
    assert c["sliding_window"] == cfg.sliding_window
    yarn = c["rope_parameters"]["full_attention"]
    assert yarn["rope_type"] == "yarn"
    assert (yarn["factor"], yarn["original_max_position_embeddings"],
            yarn["beta_fast"], yarn["beta_slow"],
            yarn["attention_factor"]) == (
        cfg.yarn_factor, cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
        cfg.yarn_beta_slow, cfg.yarn_attention_factor)
    assert yarn["rope_theta"] == c["rope_parameters"]["sliding_attention"][
        "rope_theta"] == cfg.rope_theta
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["vocab_size"], c["num_hidden_layers"], c["rms_norm_eps"]) == (
        cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        cfg.moe_d_ff, cfg.top_k, cfg.vocab, cfg.num_layers, cfg.norm_eps)
    assert cfg.router_width == 64 and cfg.num_experts == 16


def test_config_traffic_and_cell_load_and_validate():
    ctx = run.prepare("mellum2-12b-ep4.code-ctx", root=run.ROOT,
                      bench_dir=spec.BENCH_DIR, bm_root=run.ROOT,
                      require_chip=False)
    run.validate(ctx)
    assert ctx.block is BLOCK
    assert run.buckets(ctx) == [1024, 1536, 2048, 2560, 3072]
    assert run.admit_sizes(ctx) == [1, 2, 3, 4]
    bm = spec.load_benchmark()
    per_layer = {m["name"] for m in spec.cell_metrics(
        bm, "mellum2-12b-ep4.code-ctx", "per_layer")}
    assert {"moe_roofline", "reorth_roofline", "admit_mfu",
            "decode_round_ms", "device_idle"} <= per_layer
    for m in per_layer:
        assert callable(spec.metric_module(m).read)


def test_the_cells_schedule_is_the_measured_one():
    """The rate, the `mean_gap` limit and the spreads were measured on
    this schedule (0.8 req/s, a block of 32): a change to the traffic
    file or the generator that moves it shows here."""
    from bench.traffic import Source
    ctx = run.prepare("mellum2-12b-ep4.code-ctx", root=run.ROOT,
                      bench_dir=spec.BENCH_DIR, bm_root=run.ROOT,
                      require_chip=False)
    assert ctx.cell["rate_rps"] == 0.8 and ctx.traffic["block"] == 32
    src = Source(ctx.traffic, 2 ** 31 + 77, 98304, rate_rps=0.8)
    got = [(len(x.prompt), x.max_new, round(x.gap_s, 6))
           for x in map(src.item, range(6))]
    assert got == [(805, 38, 2.766216), (1500, 41, 3.186806),
                   (1115, 27, 1.175009), (768, 55, 0.440276),
                   (3072, 47, 0.989484), (2932, 61, 0.283822)]
    # most prompts outrun the 1024-row window, so the rings truncate
    lens = [len(src.item(i).prompt) for i in range(32)]
    assert sum(n > 1024 for n in lens) == 24


def test_reference_matches_the_program_at_float32():
    """The program's own mixed-cache prefill and decode at float32 weights
    against the reference, past the window: a 40-token prompt into a
    16-row ring, 12 decode steps.  The two factorize with different
    Lanczos codes (the engine's batched one and ``reference.lanczos``),
    which reach the same rank-r subspace to float32 rounding; the held
    experts are computed densely on one side and by sorted runs on the
    other.  So the logits agree to 1e-3 of their largest magnitude, and
    bfloat16 weights (the precision below) do not."""
    from repro.engine import DecomposeEngine, EngineConfig
    from repro.models import decomposed_kv as DK
    m = dict(TINY["model"], dtype="float32")
    cfg = _cfg(TINY, dtype="float32")
    params = weights.make(BLOCK.layout(m), 5, "float32")
    rank, extra, n = 8, 8, 40
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, m["vocab"], n, dtype=np.int32)
    served = [int(t) for t in rng.integers(0, m["vocab"], 13)]
    eng = DecomposeEngine(EngineConfig(backend="reference", kv_rank=rank,
                                       kv_iters_extra=extra))
    with jax.default_matmul_precision("highest"):
        lg, cache, _ = DK.prefill_dkv(params, cfg, jnp.asarray(prompt)[None],
                                      rank, tail=16, engine=eng)
        rows = [np.asarray(lg[0, :m["vocab"]])]
        for i, t in enumerate(served[:-1]):
            pos = jnp.asarray([n + i], jnp.int32)
            lg, cache = DK.decode_step_dkv(params, cfg,
                                           jnp.asarray([t], jnp.int32),
                                           cache, pos, frozen_len=n)
            rows.append(np.asarray(lg[0, :m["vocab"]]))
    prog = np.stack(rows)
    ref = BLOCK.served_logits(params, m, prompt, served, rank=rank,
                              iters=rank + extra, decode_pad=16)
    assert ref.shape == prog.shape
    err = np.abs(ref - prog).max() / np.abs(ref).max()
    assert err < 1e-3, err
    bf16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    low = BLOCK.served_logits(bf16, m, prompt, served, rank=rank,
                              iters=rank + extra, decode_pad=16)
    assert np.abs(ref - low).max() / np.abs(ref).max() > 1e-3


def test_the_tiny_cell_runs_through_the_harness():
    """The tiny cell end to end on the CPU: served through the engine's
    mixed cache, checked against the block's reference."""
    rc, res, text = run_cell("tiny-mellum.code", seed=2 ** 33 + 5,
                             seconds=3.0, bench_dir=DATA, bm_root=DATA)
    assert rc == 0, text[-2000:]
    assert res["correct"] and res["failed"] == 0
    assert res["check"]["mean_gap"]["value"] <= \
        res["check"]["mean_gap"]["limit"]


def test_counted_work():
    m = CELL_CONF["model"]
    dense = spec.block_module("dense")
    s = 1536
    fl, by = BLOCK.reorth_needed(m, s, 64, 8)
    # seven factorized layers of 512-wide K/V, each counted as the dense
    # block counts one of its layers
    one = dict(num_layers=1, d_model=2304, num_heads=32, num_kv_heads=4,
               head_dim=128, d_ff=1, vocab=1)
    dfl, dby = dense.reorth_needed(one, s, 64, 8)
    assert (fl, by) == pytest.approx((7 * dfl, 7 * dby))
    efl, eby = BLOCK.expert_needed(m, s)
    # 8 picks a token, 16 of 64 experts held: 2 expert evaluations
    assert efl == pytest.approx(28 * s * 2 * 3 * 2 * 2304 * 896)
    assert eby == pytest.approx(28 * s * 2 * 2 * 2304 * 2)
    # a decode round of 8 tokens: 2 held picks each; each held expert
    # picked by some token with probability 1 - (7/8)^8
    rfl, rby = BLOCK.expert_round_needed(m, 8)
    used = 16 * (1 - (7 / 8) ** 8)
    assert rfl == pytest.approx(28 * 16 * 3 * 2 * 2304 * 896)
    assert rby == pytest.approx(28 * 2 * (used * 3 * 2304 * 896
                                          + 16 * 2 * 2304))
    assert 3.5e9 < rby < 3.8e9            # the issue's 3.6 GB a round
    ff = BLOCK.forward_flops(m, s)
    assert 2.0e9 * s < ff < 2.6e9 * s     # ~1.9 GFLOP a token + attention
    assert BLOCK.forward_flops(m, 4096) < \
        BLOCK.forward_flops(dict(m, sliding_window=0), 4096)


def _rec(ops, prompts, steps=(), deliveries=()):
    reqs = [SimpleNamespace(prompt_len=n, deliveries=[]) for n in prompts]
    reqs.append(SimpleNamespace(prompt_len=0, deliveries=list(deliveries)))
    return SimpleNamespace(
        trace={"ops": ops}, model=CELL_CONF["model"], block=BLOCK,
        device_kind="TPU v5 lite", span=(10.0, 20.0), steps=list(steps),
        requests=reqs, admitted_in_span=lambda: reqs[:-1])


def test_moe_roofline_reads_a_recorded_op_list():
    mod = spec.metric_module("moe_roofline")
    ops = {"%ragged-dot-none.3 custom-call": 0.010,
           "%ragged-dot-none.4 custom-call": 0.006,
           "%ragged-dot-metadata.1 custom-call": 0.0004,
           "%reorth_left_batched.25 custom-call": 0.5,
           "%fusion.171 fusion": 0.2}
    fl, by = BLOCK.expert_needed(CELL_CONF["model"], 1536)
    from bench import counts
    need = 2 * counts.roofline_seconds(fl, by, "TPU v5 lite")[0]
    got = mod.read(_rec(ops, [1536, 1536]))
    assert got == pytest.approx(100 * need / 0.0164)
    assert 0 < got <= 100
    # a step of 8 rounds that delivered 1 first token and 32 decoded ones
    # (4 live a round), one before the span, one without rounds
    steps = [(12.0, 12.5, "step: admission and decode", 8),
             (5.0, 5.5, "step: decode", 8), (13.0, 13.1, "wait", 0)]
    dl = [(12.5, 17), (12.5, 16), (5.5, 8)]
    assert mod.decode_rounds(_rec(ops, [], steps, dl)) == [(8, 4.0)]
    fl4, by4 = BLOCK.expert_round_needed(CELL_CONF["model"], 4.0)
    round_s = counts.roofline_seconds(fl4, by4, "TPU v5 lite")
    assert round_s[1] == "memory"
    got = mod.read(_rec(ops, [1536, 1536], steps, dl))
    assert got == pytest.approx(100 * (need + 8 * round_s[0]) / 0.0164)
    assert mod.read(_rec({"%fusion.1 fusion": 1.0}, [1536])) is None
    assert mod.read(_rec(ops, [])) is None
    dense = SimpleNamespace(trace={"ops": ops}, block=spec.block_module(
        "dense"))
    assert mod.read(dense) is None       # a block with no experts
