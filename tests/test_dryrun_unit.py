"""Dry-run machinery units: HLO collective parsing, roofline math,
probe plans (the full sweep runs via launch.dryrun --all).

Also guards the 1-device invariant: no test may import launch.dryrun."""

import jax
import pytest

from repro.configs import all_archs, cells
# import from the side-effect-free helper module (launch.dryrun sets
# XLA_FLAGS at import — the 512-device forcing must never leak into pytest)
from repro.launch.roofline import (_RING_FACTOR, _shape_bytes,
                                   collective_stats, probe_plan,
                                   roofline_terms)


HLO = """
  %ag = bf16[16,1024]{1,0} all-gather(%p0), replica_groups={{0,1}}
  %ar.1 = f32[256,128]{1,0} all-reduce(%x), to_apply=%add
  %ars = f32[8]{0} all-reduce-start(%y), to_apply=%add
  %ard = f32[8]{0} all-reduce-done(%ars)
  %rs = (f32[64]{0}, f32[64]{0}) reduce-scatter(%a, %b), dimensions={0}
  %cp = u32[4]{0} collective-permute(%c), source_target_pairs={{0,1}}
  %dot = f32[128,128]{1,0} dot(%l, %r)
"""


def test_shape_bytes():
    assert _shape_bytes("bf16[16,1024]") == 16 * 1024 * 2
    assert _shape_bytes("(f32[64], f32[64])") == 2 * 64 * 4
    assert _shape_bytes("pred[8]") == 8


def test_collective_stats_parses_types_and_starts():
    st = collective_stats(HLO)
    assert st["all-gather"]["count"] == 1
    assert st["all-gather"]["bytes"] == 16 * 1024 * 2
    # all-reduce: plain + -start variant; -done NOT double counted
    assert st["all-reduce"]["count"] == 2
    assert st["all-reduce"]["bytes"] == 256 * 128 * 4 + 8 * 4
    assert st["reduce-scatter"]["bytes"] == 2 * 64 * 4
    assert st["collective-permute"]["count"] == 1


def test_roofline_terms_math():
    coll = {k: {"bytes": 0, "count": 0} for k in _RING_FACTOR}
    coll["all-reduce"]["bytes"] = 50e9       # 1 s at 2x ring factor -> 2 s
    t = roofline_terms(197e12, 819e9, coll)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(1.0)
    assert t["collective_s"] == pytest.approx(2.0)


def test_probe_plans_cover_all_archs():
    for name, cfg in all_archs().items():
        if name == "llama2-7b":
            continue
        plan, n_full = probe_plan(cfg)
        (p1, n1), (p2, n2) = plan
        assert n2 > n1 >= 1
        assert n_full >= n2
        assert p1.num_layers < cfg.num_layers
        # probe configs must still be structurally valid
        if cfg.family == "vlm":
            assert p1.num_layers % p1.cross_attn_period == 0
        if cfg.family == "audio":
            assert p1.enc_layers >= 1 and p1.dec_layers >= 1


def test_cells_assignment():
    """40 cells total: long_500k only for sub-quadratic archs."""
    total = 0
    for name, cfg in all_archs().items():
        if name == "llama2-7b":
            continue
        cs = cells(cfg)
        total += len(cs)
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in cs
        else:
            assert "long_500k" not in cs
    # 40 assigned cells − 8 documented long_500k skips, plus mellum2-12b's
    # three (an expert model served through the decomposed KV cache)
    assert total == 9 * 3 + 2 * 4 == 35


def test_pytest_process_sees_one_device():
    """launch.dryrun's XLA_FLAGS side effect must never leak into tests."""
    assert len(jax.devices()) == 1
