"""Compile rehearsal, for a described TPU v5e, of the re-orthogonalization
kernel at the shapes the deepseek-7b-15l long-prompt cell admits: 15
layers x 4 prompts of a 1024-token bucket, K/V 4096 wide, expansion 8.
Nothing runs.  The topology is described in a fixture, never at import:
only one process may load the TPU library, and the suite runs under
several pytest-xdist workers."""
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec  # noqa: E402

DEEPSEEK = spec.config_file("deepseek-7b-15l")
_M, _ENG = DEEPSEEK["model"], DEEPSEEK["engine"]
KVW = _M["num_kv_heads"] * (_M["d_model"] // _M["num_heads"])      # 4096
BATCH = _M["num_layers"] * 4                                         # 60
BUCKET = 1024
K = _ENG["kv_rank"] + _ENG["kv_iters_extra"]                          # 72


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("side", ["right", "left"])
def test_reorth_compiles_at_deepseek_cell_shape(one_chip, side):
    from repro.kernels import lanczos_reorth as LR
    f = _ENG["expansion"]
    assert KVW % (LR.LANE * f) == 0 and BUCKET % (LR.SUBLANE * f) == 0

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    if side == "right":
        fn, x, q = LR.reorth_right_batched, sds(BATCH, BUCKET), \
            sds(BATCH, KVW, K)
    else:
        fn, x, q = LR.reorth_left_batched, sds(BATCH, KVW), \
            sds(BATCH, BUCKET, K)
    compiled = jax.jit(lambda a, x, q: fn(a, x, q, expansion=f,
                                          interpret=False)).lower(
        sds(BATCH, BUCKET, KVW), x, q).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * BATCH * BUCKET * KVW
