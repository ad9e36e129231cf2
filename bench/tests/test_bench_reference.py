"""The plain reference runs the served path's mathematics: the dense
block's weights tree is the engine's, its Lanczos recovers a low-rank
matrix, at float32 it agrees with the program's own prefill and low-rank
decode, and its weights keep their bits, and its logits theirs to
float32 rounding, from before the block moved into
``bench/blocks/dense.py``."""
import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import reference, spec, weights  # noqa: E402
from bench.tests._tiny import DATA  # noqa: E402

TINY = spec.config_file("tiny", DATA)
DENSE = spec.block_module("dense")


def _cfg(conf, **kw):
    from repro.configs.base import get_arch
    return get_arch(conf["arch"]).replace(**conf.get("replace", {}), **kw)


@pytest.mark.parametrize("conf", [spec.config_file("granite-3-2b"), TINY],
                         ids=["granite-3-2b", "tiny"])
def test_weights_tree_is_the_engines(conf):
    from repro.models import api
    want = api.abstract_params(_cfg(conf))
    got = weights.abstract(DENSE.layout(conf["model"]), conf["dtype"])
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_weights_depend_on_the_whole_seed():
    tree = DENSE.layout(TINY["model"])
    a = weights.make(tree, 2 ** 31 + 1)["embed"]["w"]
    b = weights.make(tree, 1)["embed"]["w"]
    c = weights.make(tree, 2 ** 31 + 1)["embed"]["w"]
    assert not np.array_equal(a, b) and np.array_equal(a, c)


def test_lanczos_recovers_a_low_rank_matrix():
    rng = np.random.default_rng(0)
    a = (rng.standard_normal((96, 6)) @ rng.standard_normal((6, 40))
         ).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        us, vt = jax.jit(reference.lanczos, static_argnums=(1, 2))(
            jnp.asarray(a), 6, 10)
    rec = np.asarray(us @ vt)
    assert np.linalg.norm(rec - a) / np.linalg.norm(a) < 1e-5


def test_reference_matches_the_program_at_float32():
    """The program's own prefill and decomposed-KV decode at float32
    weights against the reference: logits agree to float32 rounding."""
    from repro.engine import DecomposeEngine, EngineConfig
    from repro.models import decomposed_kv as DK
    m = dict(TINY["model"], dtype="float32")
    cfg = _cfg(TINY, dtype="float32")
    params = weights.make(DENSE.layout(m), 5, "float32")
    rank, extra = 16, 8
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, m["vocab"], 48, dtype=np.int32)
    served = [int(t) for t in rng.integers(0, m["vocab"], 6)]
    eng = DecomposeEngine(EngineConfig(backend="reference", kv_rank=rank,
                                       kv_iters_extra=extra))
    with jax.default_matmul_precision("highest"):
        lg, cache = DK.prefill_dkv(params, cfg, jnp.asarray(prompt)[None],
                                   rank, tail=8, engine=eng)
        rows = [np.asarray(lg[0, :m["vocab"]])]
        for i, t in enumerate(served[:-1]):
            pos = jnp.asarray([48 + i], jnp.int32)
            lg, cache = DK.decode_step_dkv(params, cfg,
                                           jnp.asarray([t], jnp.int32),
                                           cache, pos, frozen_len=48)
            rows.append(np.asarray(lg[0, :m["vocab"]]))
    prog = np.stack(rows)
    ref = DENSE.served_logits(params, m, prompt, served, rank=rank,
                              iters=rank + extra, decode_pad=8)
    assert ref.shape == prog.shape
    err = np.abs(ref - prog).max() / np.abs(ref).max()
    assert err < 1e-3, err


GOLDEN = json.loads((DATA / "golden_tiny.json").read_text())


def _sha(x) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(x)).tobytes()
                          ).hexdigest()


ROWS = np.load(DATA / "golden_tiny_rows.npz")
#: the rows' gap allowed, as a share of their largest magnitude: some
#: ulps of float32, where XLA's CPU sums split by thread count differ by
#: under 1e-6, and the float8 control differs by over 0.2
ROW_TOL = 1e-5


@pytest.mark.parametrize("seed", sorted(GOLDEN["weights"], key=int))
def test_weights_and_reference_logits_keep_their_bits(seed):
    """sha256 of every weight leaf, and the float32 reference rows (and
    the float8 control's) to float32 rounding, on the tiny configuration,
    against those recorded before the move."""
    m = TINY["model"]
    params = weights.make(DENSE.layout(m), int(seed))
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    got = {jax.tree_util.keystr(k): _sha(v) for k, v in leaves}
    assert got == GOLDEN["weights"][seed]
    p = GOLDEN["prompt"]
    rng = np.random.default_rng(p["rng"])
    prompt = rng.integers(1, m["vocab"], p["len"], dtype=np.int32)
    prompt[:p["pad"]] = 0
    served = [int(t) for t in rng.integers(0, m["vocab"], p["served"])]
    for control in (False, True):
        rows = DENSE.served_logits(params, m, prompt, served,
                                   control=control, **GOLDEN["reference"])
        want = ROWS[seed + ("/control" if control else "")]
        assert rows.dtype == np.float32 and rows.shape == want.shape
        err = np.abs(rows - want).max() / np.abs(want).max()
        assert err <= ROW_TOL, (control, err)
