"""Compile the served path for a TPU v5e: the re-orthogonalization
kernels, and the prefill and decode-block programs of the benchmark's
models.

Nothing runs: the TPU compiler compiles for a described (not attached)
v5e chip, so these tests catch what interpret mode accepts and Mosaic
refuses — blocks off the (8, 128) tiling, VMEM over the scoped limit, a
program over the chip's HBM, copies of weights — at the shapes of
granite-3-2b's, deepseek-7b's (15 layers) and mellum2-12b's launches.
The topology is described inside a module fixture (never at import):
only one process may load the TPU library, and the suite runs under
several pytest-xdist workers.
"""
import collections
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.engine.config import EngineConfig
from repro.kernels import lanczos_reorth as LR
from repro.kernels import ops

GRANITE = get_arch("granite-3-2b")
KVW = GRANITE.num_kv_heads * GRANITE.resolved_head_dim     # 512
DEEPSEEK = get_arch("deepseek-7b")
DEEPSEEK_KVW = DEEPSEEK.num_kv_heads * DEEPSEEK.resolved_head_dim   # 4096
DEEPSEEK_LAYERS, DEEPSEEK_ADMIT = 15, 4                     # one stage
SLOTS, PROMPT, RANK = 8, 1024, 64
K = RANK + EngineConfig().kv_iters_extra                    # Lanczos buffer
LONG_PROMPT = 8192


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off for these tests
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_reorth(sharding, side: str, batch: int, s: int, f: int,
                    kvw: int = KVW, a_dtype=jnp.float32):
    s_pad, h_pad = ops.padded_dims(s, kvw, f)

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    if side == "right":
        fn, x, q = LR.reorth_right_batched, sds(batch, s_pad), \
            sds(batch, h_pad, K)
    else:
        fn, x, q = LR.reorth_left_batched, sds(batch, h_pad), \
            sds(batch, s_pad, K)
    compiled = jax.jit(lambda a, x, q: fn(a, x, q, expansion=f,
                                          interpret=False)).lower(
        sds(batch, s_pad, h_pad, dtype=a_dtype), x, q).compile()
    return compiled, (s_pad, h_pad)


#: A's dtype in each re-orth custom call of a compiled program
_REORTH_A = re.compile(r"%reorth_(?:right|left)_batched\S* = .*?"
                       r"custom-call\(.*?operand_layout_constraints="
                       r"\{([a-z0-9]+)\[")


@pytest.mark.parametrize("f", [4, 8, 16])
@pytest.mark.parametrize("side", ["right", "left"])
def test_reorth_compiles_at_granite_serve_shape(one_chip, side, f):
    """[40 layers x 8 slots, 1024-token bucket, 512] f32 — one admitted
    batch's K (or V) through one Lanczos pass."""
    batch = GRANITE.num_layers * SLOTS
    compiled, (s_pad, h_pad) = _compile_reorth(one_chip, side, batch,
                                               PROMPT, f)
    assert "tpu_custom_call" in compiled.as_text()
    assert (h_pad // f) % LR.LANE == 0 and (s_pad // f) % LR.SUBLANE == 0
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * batch * s_pad * h_pad


@pytest.mark.parametrize("side", ["right", "left"])
def test_reorth_compiles_at_long_prompt(one_chip, side):
    """An 8192-token prompt for one slot (40 layers): the A block is
    (8192, 128) on the right step, so the VMEM request must stay under the
    scoped cap."""
    compiled, _ = _compile_reorth(one_chip, side, GRANITE.num_layers,
                                  LONG_PROMPT, 8)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("side", ["right", "left"])
def test_reorth_compiles_at_deepseek_serve_shape(one_chip, side):
    """[15 layers x 4 admitted prompts, 1024-token bucket, 4096] f32 at
    f = 8: deepseek-7b's 4096-wide K (or V), no lane padding."""
    batch = DEEPSEEK_LAYERS * DEEPSEEK_ADMIT
    compiled, (s_pad, h_pad) = _compile_reorth(one_chip, side, batch,
                                               PROMPT, 8, DEEPSEEK_KVW)
    assert "tpu_custom_call" in compiled.as_text()
    assert (s_pad, h_pad) == (PROMPT, DEEPSEEK_KVW)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * batch * s_pad * h_pad


#: the cells' bf16 re-orth launches at f = 8, (batch, prompt rows, kv
#: width): granite-3-2b's 40 layers x 4 prompts of 512 and x 1 of 2048,
#: deepseek-7b's 15 x 4 of 512, mellum2-12b's 7 factorized layers x 4 of
#: 3072, and a 576-row prompt, whose left-step A block is 72 bf16 rows (a
#: whole number of 8-row tiles, not of 16-row bf16 ones)
BF16_LAUNCHES = {"granite-4x512": (160, 512, KVW),
                 "granite-1x2048": (40, 2048, KVW),
                 "deepseek-4x512": (60, 512, DEEPSEEK_KVW),
                 "mellum-4x3072": (28, 3072, KVW),
                 "granite-1x576": (40, 576, KVW)}


@pytest.mark.parametrize("launch", sorted(BF16_LAUNCHES))
@pytest.mark.parametrize("side", ["right", "left"])
def test_reorth_compiles_bf16_at_cell_launches(one_chip, side, launch):
    """The K/V prefill wrote, streamed as bf16: the kernel's one-pass split
    product compiles at every launch shape of the cells, and the launch
    reads A at 2 bytes an element (no f32 copy among its arguments)."""
    batch, s, kvw = BF16_LAUNCHES[launch]
    compiled, (s_pad, h_pad) = _compile_reorth(one_chip, side, batch, s, 8,
                                               kvw, jnp.bfloat16)
    text = compiled.as_text()
    assert _REORTH_A.findall(text) == ["bf16"]
    a_bytes = 2 * batch * s_pad * h_pad          # an f32 A alone is twice
    mem = compiled.memory_analysis()
    assert a_bytes <= mem.argument_size_in_bytes < 2 * a_bytes


# -- mellum2-12b (16 of 64 experts held): the expert layer and decode ------

MELLUM = get_arch("mellum2-12b").replace(num_experts=16)


@pytest.mark.parametrize("tokens", [4 * 3072, 8], ids=["prefill", "decode"])
def test_held_experts_compile_at_mellum_widths(one_chip, tokens):
    """The drop-free held-expert layer at the prefill launch of four
    3072-token prompts and at a decode round of 8 slots: XLA's grouped
    matmul (``ragged-dot``) for gate, up and down, over 16 held experts of
    2304 x 896 routed over 64."""
    from repro.models import moe
    cfg = MELLUM
    p = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: moe.moe_ffn_init(jax.random.PRNGKey(0), cfg)))
    assert p["router"]["w"].shape == (2304, 64)
    assert p["w_gate"].shape == (16, 2304, 896)
    x = jax.ShapeDtypeStruct((1, tokens, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(lambda p, x: moe.held_experts_ffn(p, x, cfg)[0]
                       ).lower(p, x).compile()
    text = compiled.as_text()
    assert text.count("ragged-dot-none") >= 3


def test_mixed_decode_block_compiles_at_mellum_widths(one_chip):
    """The fused 8-step decode block over the mixed cache: 8 slots, 21
    window rings of 1024 rows beside 7 factorized layers of 3072 prefix
    rows at rank 64 and a 128-row tail; weights and cache fit one chip's
    16 GB."""
    from repro.models import api
    from repro.models import decomposed_kv as DK
    from repro.serving import greedy_sampler
    cfg, b = MELLUM, 8
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(sds, api.abstract_params(cfg))
    cache = jax.tree_util.tree_map(sds, jax.eval_shape(
        lambda: DK.init_cache(cfg, b, 3072, RANK, tail=128)))
    assert cache["ring"]["k"].shape == (21, b, 1024, 4, 128)
    assert cache["k_u"].shape == (7, b, 3072, RANK)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)

    def run(p, t, c, pos, fl, n, stops, key, r0):
        return DK.decode_block_dkv(p, cfg, t, c, pos, fl, n, stops, key, r0,
                                   sampler=greedy_sampler, max_block=8)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(run, donate_argnums=(2,)).lower(
        params, i32(b), cache, i32(b), i32(b), i32(), i32(b, 2), key,
        i32()).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    text = compiled.as_text()
    assert "ragged-dot-none" in text
    assert _weight_copies(text, _expert_dims(params)) == {}


#: an instruction of compiled HLO text: its name, result shape and opcode
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+)\s*=\s*[a-z][a-z0-9]*"
                    r"\[([0-9,]*)\]\S*\s+([a-z][a-z0-9-]*)\(", re.M)


def _weight_copies(text: str, shapes) -> collections.Counter:
    """The instructions of a compiled program that make an array shaped
    like a layer weight (trailing two dims in ``shapes``) other than by
    naming it — anything but a parameter, a tuple element or a bitcast —
    counted by (result dims, opcode)."""
    found = collections.Counter()
    for _, dims, op in _INSTR.findall(text):
        tail = tuple(int(d) for d in dims.split(",")[-2:] if d)
        if tail in shapes and op not in (
                "parameter", "get-tuple-element", "bitcast"):
            found[(dims, op)] += 1
    return found


def _trailing_dims(tree):
    """The trailing two dims of every stacked leaf (rank >= 3) of a tree
    of layer weights."""
    return {tuple(a.shape[-2:]) for a in jax.tree_util.tree_leaves(tree)
            if a.ndim >= 3}


def _expert_dims(params):
    return _trailing_dims({w: params["layers"]["moe"][w]
                           for w in ("w_gate", "w_up", "w_down")})


@pytest.mark.parametrize("batch, prompt", [(1, 1536), (4, 3072)])
def test_mixed_prefill_compiles_at_mellum_widths(one_chip, batch, prompt):
    """The mixed prefill over two periods (8 layers) at published widths,
    for one 1536-token prompt (whose row gather the compiler once refused
    for scoped VMEM) and the cell's largest admission: the grouped
    matmuls read the layer stacks of expert weights where they lie, with
    no slice of them copied out."""
    from repro.models import api
    from repro.models import decomposed_kv as DK
    cfg = MELLUM.replace(num_layers=8)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    params = jax.tree_util.tree_map(sds, api.abstract_params(cfg))
    assert params["layers"]["moe"]["w_down"].shape == (8, 16, 896, 2304)
    toks = jax.ShapeDtypeStruct((batch, prompt), jnp.int32,
                                sharding=one_chip)
    compiled = jax.jit(lambda p, t: DK.prefill_dkv(p, cfg, t, RANK)
                       ).lower(params, toks).compile()
    text = compiled.as_text()
    assert "ragged-dot-none" in text
    assert _weight_copies(text, _expert_dims(params)) == {}


# -- the dense cells: granite-3-2b and deepseek-7b (15 layers) -------------

#: per cell: (config, prefix rows of its cache: ``max_len`` less the tail)
DENSE_CELLS = {"granite-3-2b": (GRANITE, 2048),
               "deepseek-7b-15l": (DEEPSEEK.replace(num_layers=15), 1024)}

#: the weight-shaped instructions, by (result dims, opcode), that each
#: dense program compiled to when dense models had layer bodies of their
#: own beside the layer-kind scan.  A program may not take on more: the
#: whole-stack copies here are the decode block's known cost, not a
#: budget to grow.
DENSE_WEIGHT_SHAPED = {
    ("granite-3-2b", "decode"): {
        ("1,2048,2048", "dynamic-slice"): 2,
        ("1,2048,2048", "fusion"): 1,
        ("1,2048,512", "dynamic-slice"): 2,
        ("1,2048,512", "fusion"): 2,
        ("1,2048,8192", "dynamic-slice"): 2,
        ("1,8192,2048", "dynamic-slice"): 1,
        ("10,2048,512", "slice-done"): 4,
        ("2048,2048", "fusion"): 1,
        ("2048,8192", "fusion"): 2,
        ("40,2048,2048", "copy"): 1,
        ("40,2048,512", "copy"): 2,
        ("40,2048,512", "custom-call"): 1,
        ("8192,2048", "fusion"): 1,
    },
    ("granite-3-2b", "prefill"): {
        ("1,2048,2048", "copy"): 1,
        ("1,2048,2048", "dynamic-slice"): 2,
        ("1,2048,2048", "fusion"): 1,
        ("1,2048,512", "copy"): 1,
        ("1,2048,512", "dynamic-slice"): 2,
        ("1,2048,512", "fusion"): 1,
        ("1,2048,8192", "dynamic-slice"): 2,
        ("1,8192,2048", "dynamic-slice"): 1,
        # [4 x 512 tokens, d_model] activations share granite's [2048, 2048]
        ("2048,2048", "broadcast"): 1,
        ("2048,2048", "convert"): 3,
        ("2048,2048", "fusion"): 2,
        ("2048,2048", "multiply"): 1,
        ("2048,2048", "reshape"): 1,
    },
    ("deepseek-7b-15l", "decode"): {
        ("1,11008,4096", "dynamic-slice"): 1,
        ("1,4096,11008", "dynamic-slice"): 2,
        ("1,4096,4096", "dynamic-slice"): 4,
        ("1,4096,4096", "fusion"): 3,
        ("11008,4096", "fusion"): 1,
        ("15,4096,4096", "copy"): 3,
        ("4096,11008", "fusion"): 2,
        ("4096,4096", "fusion"): 1,
    },
    ("deepseek-7b-15l", "prefill"): {
        ("1,11008,4096", "dynamic-slice"): 1,
        ("1,4096,11008", "dynamic-slice"): 2,
        ("1,4096,4096", "copy"): 2,
        ("1,4096,4096", "dynamic-slice"): 4,
        ("1,4096,4096", "fusion"): 2,
    },
}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("cell", sorted(DENSE_CELLS))
def test_dense_programs_compile_at_cell_shapes(one_chip, cell, program):
    """The fused 8-step decode block over 8 slots (the cell's prefix rows
    at rank 64, a 128-row tail), and the prefill of four 512-token
    prompts through the compiled Lanczos kernel: each fits one chip's
    16 GB and makes no more weight-shaped arrays than in the table."""
    from repro.engine import DecomposeEngine
    from repro.models import api
    from repro.models import decomposed_kv as DK
    from repro.serving import greedy_sampler
    cfg, rows = DENSE_CELLS[cell]
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    params = jax.tree_util.tree_map(sds, api.abstract_params(cfg))
    if program == "decode":
        b = SLOTS
        cache = jax.tree_util.tree_map(sds, jax.eval_shape(
            lambda: DK.init_cache(cfg, b, rows, RANK, tail=128)))
        assert cache["k_u"].shape == (cfg.num_layers, b, rows, RANK)
        assert "ring" not in cache

        def run(p, t, c, pos, fl, n, stops, key, r0):
            return DK.decode_block_dkv(p, cfg, t, c, pos, fl, n, stops, key,
                                       r0, sampler=greedy_sampler,
                                       max_block=8)

        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
        compiled = jax.jit(run, donate_argnums=(2,)).lower(
            params, i32(b), cache, i32(b), i32(b), i32(), i32(b, 2), key,
            i32()).compile()
    else:
        engine = DecomposeEngine(EngineConfig(
            backend="pallas", expansion=8, kv_rank=RANK,
            kv_iters_extra=EngineConfig().kv_iters_extra))
        toks = i32(4, 512)
        compiled = jax.jit(lambda p, t: DK.prefill_dkv(
            p, cfg, t, RANK, tail=128, engine=engine)).lower(
            params, toks).compile()
        assert "tpu_custom_call" in compiled.as_text()
        # the re-orth kernel streams the bf16 K/V prefill wrote
        a_dtypes = _REORTH_A.findall(compiled.as_text())
        assert a_dtypes and set(a_dtypes) == {"bf16"}
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    got = _weight_copies(compiled.as_text(), _trailing_dims(params["layers"]))
    table = DENSE_WEIGHT_SHAPED[(cell, program)]
    over = {k: (n, table.get(k, 0)) for k, n in got.items()
            if n > table.get(k, 0)}
    assert over == {}, f"weight-shaped instructions (now, before): {over}"
