"""DecomposeEngine: backend parity, padding exactness, consumer regression.

Acceptance checks for the unified pipeline:
* jnp reference vs Pallas-interpret BATCHED backend agree across rank/batch/
  dtype (the batched fused kernel is numerically the same algorithm);
* the batched backend issues ONE kernel launch over the whole batch (the
  hooks are the native batched ones, not a vmap lift);
* decomposed_kv prefill through the engine matches the pre-engine
  per-callsite path (lz.decompose directly);
* pad-plan caching in kernels.ops is hit, not recomputed.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lanczos as lz
from repro.core.policy import DecompositionPolicy, LayerPolicy
from repro.engine import (DecomposeEngine, EngineConfig, available_backends,
                          get_backend)
from repro.kernels import ops


def _x(key, b, s, h, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), (b, s, h),
                             jnp.float32).astype(dtype)


@pytest.fixture(scope="module")
def engines():
    return {name: DecomposeEngine(EngineConfig(backend=name))
            for name in ("reference", "pallas_interpret", "pallas_vmap")}


# ---------------------------------------------------------------------------
# Backend parity: reference vs batched Pallas kernels
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rank", [1, 4, 8])
@pytest.mark.parametrize("batch", [1, 4])
def test_parity_reference_vs_pallas_f32(engines, rank, batch):
    x = _x(rank * 10 + batch, batch, 32, 64, jnp.float32)
    lr_ref = engines["reference"].decompose(x, rank)
    lr_pal = engines["pallas_interpret"].decompose(x, rank)
    np.testing.assert_allclose(np.asarray(lr_ref.reconstruct()),
                               np.asarray(lr_pal.reconstruct()),
                               rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(np.asarray(lr_ref.core),
                               np.asarray(lr_pal.core), rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("rank", [2, 8])
@pytest.mark.parametrize("batch", [2, 3])
def test_parity_reference_vs_pallas_bf16(engines, rank, batch):
    x = _x(rank * 100 + batch, batch, 32, 64, jnp.bfloat16)
    lr_ref = engines["reference"].decompose(x, rank)
    lr_pal = engines["pallas_interpret"].decompose(x, rank)
    assert lr_pal.u.dtype == jnp.bfloat16
    # both paths upcast to fp32 internally; bf16 output rounding dominates
    np.testing.assert_allclose(
        np.asarray(lr_ref.reconstruct(), np.float32),
        np.asarray(lr_pal.reconstruct(), np.float32), rtol=3e-2, atol=3e-2)


def test_parity_on_nondivisible_shapes_via_pad_plan(engines):
    """33×48 does not divide f=8 on S: the engine pads through the cached
    plan and slices back; padded vs unpadded must be the SAME math because
    the start vector is zero-extended."""
    x = _x(5, 2, 33, 48, jnp.float32)
    lr_ref = engines["reference"].decompose(x, 6)
    lr_pal = engines["pallas_interpret"].decompose(x, 6)
    assert lr_pal.u.shape == (2, 33, 6) and lr_pal.vt.shape == (2, 6, 48)
    np.testing.assert_allclose(np.asarray(lr_ref.reconstruct()),
                               np.asarray(lr_pal.reconstruct()),
                               rtol=5e-3, atol=5e-3)


def test_batched_backend_is_native_not_vmap(engines):
    """The acceptance property: the pallas backends run ONE batched launch
    per Lanczos pass — their hooks are kernels.ops batched hooks, distinct
    from the vmap-of-scalar lift used by the fallback backend."""
    batched = ops.make_batched_pallas_hooks(8, interpret=True)
    assert engines["pallas_interpret"]._hooks is batched
    assert get_backend("pallas_interpret").batched_launch
    assert not get_backend("pallas_vmap").batched_launch
    assert engines["pallas_vmap"]._hooks is not batched
    # and the native batched hook really consumes the whole batch at once
    a = _x(1, 3, 32, 64, jnp.float32)
    u = jax.random.normal(jax.random.PRNGKey(2), (3, 32))
    vbuf = jnp.zeros((3, 64, 4))
    z = batched.right_step(a, u, vbuf)
    assert z.shape == (3, 64)


@pytest.mark.parametrize("rank", [4, 8])
@pytest.mark.parametrize("batch", [1, 3])
def test_parity_decompose_kv_reference_vs_pallas(engines, rank, batch):
    """The serving KV factorization rides the same backend matrix: the
    (U·Σ, Vᵀ) product must agree between the jnp reference and the batched
    Pallas-interpret backend."""
    x = _x(rank * 7 + batch, batch, 32, 64, jnp.float32)
    u_r, vt_r = engines["reference"].decompose_kv(x, rank)
    u_p, vt_p = engines["pallas_interpret"].decompose_kv(x, rank)
    assert u_p.shape == (batch, 32, rank) and vt_p.shape == (batch, rank, 64)
    np.testing.assert_allclose(
        np.asarray(jnp.einsum("btr,brh->bth", u_r, vt_r)),
        np.asarray(jnp.einsum("btr,brh->bth", u_p, vt_p)),
        rtol=5e-3, atol=5e-3)
    # exact=True bypasses the backend entirely — identical across backends
    e_r = engines["reference"].decompose_kv(x, rank, exact=True)
    e_p = engines["pallas_interpret"].decompose_kv(x, rank, exact=True)
    np.testing.assert_allclose(np.asarray(e_r[0]), np.asarray(e_p[0]),
                               rtol=1e-5, atol=1e-5)


def test_parity_splice_admission_cache_across_backends():
    """Per-slot splice admission through the serving engine produces the
    same decomposed-KV cache (as an operator: U·Vᵀ, and the dense tail)
    under the reference and pallas_interpret backends."""
    from repro.configs import all_archs
    from repro.models import model_fns
    from repro.serving import Engine, Request

    cfg = all_archs()["deepseek-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, n, dtype=np.int32) for n in (10, 6)]

    caches = {}
    for backend in ("reference", "pallas_interpret"):
        eng = Engine(cfg, params, slots=2, max_len=64,
                     decompose_engine=DecomposeEngine(EngineConfig(
                         backend=backend, kv_rank=8, kv_tail=4)))
        eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=8))
        for step in range(12):
            if step == 2:   # splice-admit while slot 0 is live
                eng.submit(Request(uid=1, prompt=prompts[1],
                                   max_new_tokens=6))
            eng.step()
        caches[backend] = eng.cache
        np.testing.assert_array_equal(eng.frozen_len >= 16, True)
    a, b = caches["reference"], caches["pallas_interpret"]
    for uk, vk in (("k_u", "k_vt"), ("v_u", "v_vt")):
        np.testing.assert_allclose(
            np.asarray(jnp.einsum("lbtr,lbrh->lbth", a[uk], a[vk])),
            np.asarray(jnp.einsum("lbtr,lbrh->lbth", b[uk], b[vk])),
            rtol=5e-2, atol=5e-2)
    for k in ("k", "v"):
        np.testing.assert_allclose(np.asarray(a["tail"][k]),
                                   np.asarray(b["tail"][k]),
                                   rtol=5e-2, atol=5e-2)


def test_vmap_fallback_matches_batched_kernels(engines):
    x = _x(9, 4, 32, 64, jnp.float32)
    lr_v = engines["pallas_vmap"].decompose(x, 5)
    lr_b = engines["pallas_interpret"].decompose(x, 5)
    np.testing.assert_allclose(np.asarray(lr_v.reconstruct()),
                               np.asarray(lr_b.reconstruct()),
                               rtol=5e-3, atol=5e-3)


def test_hook_cache_does_not_freeze_interpret_flag():
    """Flipping ops.INTERPRET after a cached interpret=None resolution must
    yield different hooks (the TPU-deployment contract in ops.py's
    docstring), while equal resolved configs share one identity."""
    h_default = ops.make_batched_pallas_hooks(8)        # resolves INTERPRET
    assert h_default is ops.make_batched_pallas_hooks(8, interpret=True)
    try:
        ops.INTERPRET = False
        assert ops.make_batched_pallas_hooks(8) is not h_default
        assert ops.make_batched_pallas_hooks(8) is \
            ops.make_batched_pallas_hooks(8, interpret=False)
    finally:
        ops.INTERPRET = True
    assert ops.make_batched_pallas_hooks(8) is h_default


def test_pad_plan_is_cached():
    ops.pad_plan.cache_clear()
    ops.padded_dims.cache_clear()
    for _ in range(5):
        assert ops.padded_dims(33, 48, 8) == (64, 1024)
        ops.pad_plan((2, 33, 48), 1, 8)
    assert ops.padded_dims.cache_info().hits >= 4
    assert ops.pad_plan.cache_info().hits >= 4


# ---------------------------------------------------------------------------
# Policy / outlier pipeline through the engine
# ---------------------------------------------------------------------------

def test_decompose_activation_matches_manual_pipeline():
    """Engine pipeline == hand-wired extract → decompose → attach (the old
    per-callsite decomposed.decompose_activation body)."""
    from repro.core import outlier as ol
    pol = DecompositionPolicy.from_layer_list(4, [0], rank=6,
                                              outlier_frac=0.05, iters=10)
    eng = DecomposeEngine(EngineConfig(policy=pol))
    x = _x(11, 2, 32, 64, jnp.float32)
    got = eng.decompose_activation(x, 0)

    lp = pol.layer(0)
    thr = pol.thresholds.get(0)
    num_c = max(1, round(lp.outlier_frac * 64))
    base, vals, idx = ol.extract(x, jnp.asarray(thr, jnp.float32), num_c)
    want = lz.decompose(base, lp.rank, iters=lp.effective_iters)
    want = ol.attach_dense_outliers(want, vals, idx)
    np.testing.assert_allclose(np.asarray(got.reconstruct()),
                               np.asarray(want.reconstruct()),
                               rtol=1e-4, atol=1e-4)
    assert got.o_idx is not None and got.o_idx.shape[-1] == num_c


def test_engine_config_layer_fallbacks():
    eng = DecomposeEngine(EngineConfig())       # no policy
    assert eng.layer_policy(3) == LayerPolicy(decompose=False)
    assert eng.threshold(3) == 6.0              # ThresholdTable default


# ---------------------------------------------------------------------------
# Consumer regression: decomposed_kv prefill through the engine
# ---------------------------------------------------------------------------

def test_dkv_prefill_engine_matches_per_callsite_path():
    """prefill_dkv (engine-threaded) reproduces the pre-engine path that
    called lz.decompose at the callsite with iters = min(r+8, dims)."""
    from repro.configs import all_archs
    from repro.models import decomposed_kv as DK
    from repro.models import model_fns
    from repro.models import transformer as T

    cfg = all_archs()["deepseek-7b"].reduced()
    fns = model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    rank = 4

    eng = DecomposeEngine(EngineConfig(kv_rank=rank))
    logits, cache = DK.prefill_dkv(params, cfg, toks, rank, tail=8,
                                   engine=eng)

    # the old per-callsite computation, inlined
    _, dense_cache = T.prefill(params, cfg, toks, 16)
    kvw = cfg.num_kv_heads * cfg.resolved_head_dim
    flat = dense_cache["k"].reshape(cfg.num_layers * 2, 16, kvw)
    lr = lz.decompose(flat.astype(jnp.float32), rank,
                      iters=min(rank + 8, min(flat.shape[-2:])))
    k_u_old = lr.scaled_u().astype(flat.dtype) \
        .reshape(cfg.num_layers, 2, 16, rank)
    np.testing.assert_allclose(np.asarray(cache["k_u"], np.float32),
                               np.asarray(k_u_old, np.float32),
                               rtol=1e-3, atol=1e-3)


def test_runtime_steps_thread_engine():
    from repro.configs import all_archs
    from repro.models import model_fns
    from repro.runtime import steps

    cfg = all_archs()["llama2-7b"].reduced()
    params = model_fns(cfg).init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    pol = DecompositionPolicy.from_layer_list(cfg.num_layers, [0], rank=4)
    fwd = steps.make_decomposed_forward_step(
        cfg, EngineConfig(policy=pol))
    out = fwd(params, toks)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_backend_registry_rejects_unknown():
    with pytest.raises(KeyError):
        get_backend("no-such-backend")
    assert {"reference", "pallas", "pallas_interpret",
            "pallas_vmap"} <= set(available_backends())


def test_mesh_engine_decompose_matches_unsharded():
    """The mesh path (explicit in/out shardings on the jitted Lanczos
    pipeline; shard_map for kernel backends) reconstructs the same
    operator as the single-device engine — on a 1×1 mesh the graphs are
    identical, and the output factors carry the mesh's sharding."""
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 24, 40), jnp.float32)
    for backend in ("reference", "pallas_interpret"):
        e0 = DecomposeEngine(EngineConfig(backend=backend))
        e1 = DecomposeEngine(EngineConfig(backend=backend, mesh=mesh))
        lr0, lr1 = e0.decompose(x, 5), e1.decompose(x, 5)
        r0 = np.einsum("bsr,br,brh->bsh", *(np.asarray(a, np.float32)
             for a in (lr0.u, lr0.core, lr0.vt)))
        r1 = np.einsum("bsr,br,brh->bsh", *(np.asarray(a, np.float32)
             for a in (lr1.u, lr1.core, lr1.vt)))
        np.testing.assert_allclose(r1, r0, rtol=1e-5, atol=1e-5)
        assert lr1.u.sharding.mesh.shape == mesh.shape
    # decompose_kv rides the same path
    e1 = DecomposeEngine(EngineConfig(kv_rank=6, mesh=mesh))
    u, vt = e1.decompose_kv(x, 6)
    assert u.shape == (4, 24, 6) and vt.shape == (4, 6, 40)


def test_padded_z0_is_host_value():
    """The start-vector cache holds HOST numpy (jit places it per call
    site), never a committed device array — regression for the device-
    buffer leak / wrong-device-under-mesh bug."""
    from repro.engine.engine import _padded_z0
    z = _padded_z0(24, 32)
    assert isinstance(z, np.ndarray) and not isinstance(z, jax.Array)
    assert z.shape == (32,) and (z[24:] == 0).all()
    # identical to what the jitted core generates for the unpadded width
    ref = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (24,),
                                       jnp.float32))
    np.testing.assert_array_equal(z[:24], ref)
    # and usable under an outer trace (the jitted dkv prefill case)
    out = jax.jit(lambda: jnp.asarray(_padded_z0(24, 32)) * 2.0)()
    np.testing.assert_allclose(np.asarray(out), z * 2.0)
