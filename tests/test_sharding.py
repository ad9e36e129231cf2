"""Sharding rule tables (pure: evaluated against an AbstractMesh).

The 8-device meshes below mirror the forced-host-platform serving mesh the
CI distributed job runs (``XLA_FLAGS=--xla_force_host_platform_device_count
=8``, ``launch.mesh.make_host_mesh(8, 1)`` / ``(2, 4)``); the rules are
shape-only so AbstractMesh evaluates them without devices.  Hypothesis
properties for the same rules live in tests/test_properties.py.
"""
import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import all_archs
from repro.distributed import sharding as sh

# AbstractMesh takes (axis_sizes, axis_names)
MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
# host-platform serving meshes (8 forced devices)
MESH8 = AbstractMesh((8, 1), ("data", "model"))
MESH8_2D = AbstractMesh((2, 4), ("data", "model"))


def test_dp_axes():
    assert sh.dp_axes(MESH) == ("data",)
    assert sh.dp_axes(MESH_POD) == ("pod", "data")


def test_col_row_parallel_rules():
    cfg = all_archs()["deepseek-7b"]
    assert sh.param_spec("layers/attn/wq/w", (30, 4096, 4096), MESH, cfg) \
        == P(None, None, "model")
    assert sh.param_spec("layers/attn/wo/w", (30, 4096, 4096), MESH, cfg) \
        == P(None, "model", None)
    assert sh.param_spec("layers/mlp/down/w", (30, 11008, 4096), MESH, cfg) \
        == P(None, "model", None)
    assert sh.param_spec("embed/w", (102400, 4096), MESH, cfg) \
        == P("model", None)


def test_indivisible_dims_fall_back_to_replication():
    cfg = all_archs()["granite-3-2b"]
    # granite vocab 49155 is not 16-divisible *unpadded*; rule must not shard
    assert sh.param_spec("embed/w", (49155, 2048), MESH, cfg) == P(None, None)
    # but the PADDED table (49280) shards fine
    assert sh.param_spec("embed/w", (49280, 2048), MESH, cfg) \
        == P("model", None)


def test_moe_expert_sharding():
    olmoe = all_archs()["olmoe-1b-7b"]
    kimi = all_archs()["kimi-k2-1t-a32b"]
    assert sh.param_spec("layers/moe/w_gate", (16, 64, 2048, 1024), MESH,
                         olmoe) == P(None, "model", None, None)
    assert sh.param_spec("layers/moe/w_gate", (60, 384, 7168, 2048), MESH,
                         kimi) == P(None, "model", None, "data")
    assert sh.param_spec("layers/moe/w_down", (60, 384, 2048, 7168), MESH,
                         kimi) == P(None, "model", "data", None)


def test_zero1_adds_dp_axis():
    cfg = all_archs()["deepseek-7b"]
    spec = sh._zero1(P(None, None, "model"), (30, 4096, 4096), MESH)
    assert spec == P(None, "data", "model")


def test_cache_rules_batch_vs_sequence():
    cfg = all_archs()["deepseek-7b"]
    # decode_32k-style cache: batch 128 → DP on batch, kvh on model
    cache = {"k": jax.ShapeDtypeStruct((30, 128, 32768, 32, 128),
                                       jnp.bfloat16)}
    shd = sh.cache_sharding(cache, MESH, cfg)
    assert shd["k"].spec == P(None, "data", None, "model", None)
    # long_500k-style (batch 1) → sequence-sharded KV
    cache1 = {"k": jax.ShapeDtypeStruct((30, 1, 524288, 32, 128),
                                        jnp.bfloat16)}
    shd1 = sh.cache_sharding(cache1, MESH, cfg)
    assert shd1["k"].spec == P(None, None, "data", "model", None)


def test_mqa_head_dim_fallback():
    cfg = all_archs()["gemma-2b"]
    # kv heads == 1 -> shard head_dim (256) instead
    cache = {"k": jax.ShapeDtypeStruct((18, 128, 32768, 1, 256),
                                       jnp.bfloat16)}
    shd = sh.cache_sharding(cache, MESH, cfg)
    assert shd["k"].spec == P(None, "data", None, None, "model")


def test_ssm_cache_rules():
    cfg = all_archs()["mamba2-780m"]
    st = {"ssm": jax.ShapeDtypeStruct((48, 128, 48, 64, 128), jnp.float32),
          "conv": jax.ShapeDtypeStruct((48, 128, 3, 3328), jnp.bfloat16)}
    shd = sh.cache_sharding(st, MESH, cfg)
    assert shd["ssm"].spec == P(None, "data", "model", None, None)
    assert shd["conv"].spec == P(None, "data", None, "model")


def test_dkv_cache_rules_host8():
    """Low-rank KV leaves on the 8-device serving mesh: k_u/v_u batch→DP
    with the time axis model-REPLICATED (refuted §Perf C3), k_vt/v_vt
    batch→DP + kvw→model when divisible."""
    cfg = all_archs()["deepseek-7b"].reduced()
    cache = {"k_u": jax.ShapeDtypeStruct((2, 8, 24, 8), jnp.float32),
             "v_u": jax.ShapeDtypeStruct((2, 8, 24, 8), jnp.float32),
             "k_vt": jax.ShapeDtypeStruct((2, 8, 8, 64), jnp.float32),
             "v_vt": jax.ShapeDtypeStruct((2, 8, 8, 64), jnp.float32),
             "tail": {"k": jax.ShapeDtypeStruct((2, 8, 4, 2, 32),
                                                jnp.float32)}}
    shd = sh.cache_sharding(cache, MESH8, cfg)
    assert shd["k_u"].spec == P(None, "data", None, None)
    assert shd["v_u"].spec == P(None, "data", None, None)
    assert shd["k_vt"].spec == P(None, "data", None, "model")
    assert shd["v_vt"].spec == P(None, "data", None, "model")
    # dense tail rides the k/v rule: batch→DP, kvh→model (2 heads on 4-way
    # model doesn't divide → head_dim fallback on the 2D mesh)
    assert shd["tail"]["k"].spec == P(None, "data", None, "model", None)
    shd2 = sh.cache_sharding(cache, MESH8_2D, cfg)
    assert shd2["k_vt"].spec == P(None, "data", None, "model")   # 64 % 4 == 0
    assert shd2["tail"]["k"].spec == P(None, "data", None, None, "model")


def test_dkv_batch1_time_axis_sharding():
    """global_batch == 1: k_u's TIME axis shards over "data" instead
    (flash-decoding style), and an indivisible time axis replicates."""
    cache = {"k_u": jax.ShapeDtypeStruct((4, 1, 64, 8), jnp.float32)}
    assert sh.cache_sharding(cache, MESH8, None)["k_u"].spec \
        == P(None, None, "data", None)
    odd = {"k_u": jax.ShapeDtypeStruct((4, 1, 63, 8), jnp.float32)}
    assert sh.cache_sharding(odd, MESH8, None)["k_u"].spec \
        == P(None, None, None, None)


def test_cache_indivisible_batch_replicates_host8():
    """slots that don't divide the 8-way DP axis fall back to replication
    (the guard every mesh-serving engine relies on for odd slot counts)."""
    for b in (3, 5, 6):
        cache = {"k_u": jax.ShapeDtypeStruct((2, b, 24, 8), jnp.float32),
                 "k": jax.ShapeDtypeStruct((2, b, 24, 2, 32), jnp.float32)}
        shd = sh.cache_sharding(cache, MESH8, None)
        assert shd["k_u"].spec[1] is None, b
        assert shd["k"].spec[1] is None, b


def test_zero1_picks_first_divisible_dim_host8():
    """_zero1 adds DP to the FIRST unsharded dim divisible by the DP size,
    skipping already-sharded dims and indivisible ones."""
    assert sh._zero1(P(), (8, 32), MESH8) == P("data", None)
    assert sh._zero1(P(), (3, 32), MESH8) == P(None, "data")     # skip 3
    assert sh._zero1(P("model"), (8, 32), MESH8_2D) == P("model", "data")
    assert sh._zero1(P(), (3, 5, 7), MESH8) == P(None, None, None)  # none fit
    # dim == 1 is never picked even though 1 % 8 != 0 guards it anyway
    assert sh._zero1(P(), (1, 16), MESH8) == P(None, "data")


def test_param_spec_divisibility_fallback_host8():
    cfg = all_archs()["deepseek-7b"]
    # 4096 divides both 1 and 4 model axes → column-parallel
    assert sh.param_spec("layers/attn/wq/w", (2, 4096, 4096), MESH8_2D, cfg) \
        == P(None, None, "model")
    # a 6-wide output dim doesn't divide model=4 → replicated
    assert sh.param_spec("layers/attn/wq/w", (2, 4096, 6), MESH8_2D, cfg) \
        == P(None, None, None)


def test_constrain_cache_noop_without_mesh():
    cache = {"k_u": jnp.zeros((2, 4, 8, 3))}
    assert sh.constrain_cache(cache, None) is cache


def test_seq_shard_gate_for_fresh_serving_caches():
    """seq_shard=False (the serving engine's setting) disables the batch-1
    time-axis rule: a freshly prefilled single-request cache stays
    replicated instead of bouncing through a sequence reshard per
    admission; batch>1 DP sharding is unaffected."""
    one = {"k_u": jax.ShapeDtypeStruct((2, 1, 16, 8), jnp.float32),
           "k": jax.ShapeDtypeStruct((2, 1, 16, 2, 32), jnp.float32)}
    on = sh.cache_sharding(one, MESH8, None)
    off = sh.cache_sharding(one, MESH8, None, seq_shard=False)
    assert on["k_u"].spec[2] == "data" and on["k"].spec[2] == "data"
    assert off["k_u"].spec[2] is None and off["k"].spec[2] is None
    many = {"k_u": jax.ShapeDtypeStruct((2, 8, 16, 8), jnp.float32)}
    assert sh.cache_sharding(many, MESH8, None, seq_shard=False)[
        "k_u"].spec[1] == "data"


def test_params_sharding_full_tree():
    """Every leaf of every arch gets a spec whose sharded dims divide."""
    for name, cfg in all_archs().items():
        shapes = jax.eval_shape(
            lambda: __import__("repro.models.api", fromlist=["api"])
            .abstract_params(cfg))
        tree = sh.params_sharding(
            __import__("repro.models.api", fromlist=["api"])
            .abstract_params(cfg), MESH, cfg)
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        shapes_flat, _ = jax.tree_util.tree_flatten_with_path(
            __import__("repro.models.api", fromlist=["api"])
            .abstract_params(cfg))
        for (pth, shd), (_, leaf) in zip(flat, shapes_flat):
            for dim, axis in zip(leaf.shape, shd.spec + (None,) * 8):
                if axis is not None:
                    sz = MESH.shape[axis] if isinstance(axis, str) else \
                        int(jnp.prod(jnp.asarray([MESH.shape[a]
                                                  for a in axis])))
                    assert dim % sz == 0, (name, pth, leaf.shape, shd.spec)
