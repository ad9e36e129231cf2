"""Per-kernel allclose vs ref.py across shape/dtype sweeps (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


SHAPES = [(64, 128), (128, 512), (256, 384)]
DTYPES = [jnp.float32, jnp.bfloat16]
EXPANSIONS = [2, 4, 8]


def _mk(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("f", [4, 8])
def test_matvec(shape, dtype, f):
    s, h = shape
    a = _mk(jax.random.PRNGKey(0), (s, h), dtype)
    v = _mk(jax.random.PRNGKey(1), (h,), dtype)
    got = ops.matvec(a, v, expansion=f)
    want = ref.matvec(a, v)
    np.testing.assert_allclose(got, want, rtol=3e-2 if dtype == jnp.bfloat16
                               else 1e-5, atol=1e-1)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("f", EXPANSIONS)
def test_rmatvec(shape, f):
    s, h = shape
    a = _mk(jax.random.PRNGKey(2), (s, h), jnp.float32)
    u = _mk(jax.random.PRNGKey(3), (s,), jnp.float32)
    np.testing.assert_allclose(ops.rmatvec(a, u, expansion=f),
                               ref.rmatvec(a, u), rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("s,h", [(64, 128), (520, 128)])  # 520 % 512 != 0
def test_matvec_batched(b, s, h):
    """One launch over the batch == per-element oracle (incl. a row count
    that is NOT divisible by the default row_block)."""
    a = _mk(jax.random.PRNGKey(40), (b, s, h), jnp.float32)
    v = _mk(jax.random.PRNGKey(41), (b, h), jnp.float32)
    u = _mk(jax.random.PRNGKey(42), (b, s), jnp.float32)
    y = ops.matvec_batched(a, v, expansion=4)
    z = ops.rmatvec_batched(a, u, expansion=4)
    for i in range(b):
        np.testing.assert_allclose(np.asarray(y[i]),
                                   np.asarray(ref.matvec(a[i], v[i])),
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(np.asarray(z[i]),
                                   np.asarray(ref.rmatvec(a[i], u[i])),
                                   rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("k", [4, 12])
def test_reorth_batched_matches_scalar(b, k):
    """Batched fused re-orth (grid (B,3,f)) == the scalar kernel per prompt."""
    s, h, f = 64, 128, 8
    a = _mk(jax.random.PRNGKey(50), (b, s, h), jnp.float32)
    u = _mk(jax.random.PRNGKey(51), (b, s), jnp.float32)
    v = _mk(jax.random.PRNGKey(52), (b, h), jnp.float32)
    qv = jnp.stack([jnp.linalg.qr(_mk(jax.random.PRNGKey(53 + i),
                                      (h, k), jnp.float32))[0]
                    for i in range(b)])
    qu = jnp.stack([jnp.linalg.qr(_mk(jax.random.PRNGKey(63 + i),
                                      (s, k), jnp.float32))[0]
                    for i in range(b)])
    z, zn = ops.reorth_right_batched(a, u, qv, expansion=f)
    w, wn = ops.reorth_left_batched(a, v, qu, expansion=f)
    for i in range(b):
        z_i, zn_i = ops.reorth_right(a[i], u[i], qv[i], expansion=f)
        w_i, wn_i = ops.reorth_left(a[i], v[i], qu[i], expansion=f)
        np.testing.assert_allclose(np.asarray(z[i]), np.asarray(z_i),
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(np.asarray(w[i]), np.asarray(w_i),
                                   rtol=1e-5, atol=1e-3)
        np.testing.assert_allclose(float(zn[i]), float(zn_i), rtol=1e-5)
        np.testing.assert_allclose(float(wn[i]), float(wn_i), rtol=1e-5)


@pytest.mark.parametrize("f", [1, 2, 4, 8])
@pytest.mark.parametrize("side", ["right", "left"])
def test_reorth_a_fetched_once_per_block(side, f):
    """Walk A's index map over the (B, 3, f) grid in grid order: pass 0
    visits block j at step j, and the index changes B·f times, so each A
    block moves HBM→VMEM once per batch element, not once per pass."""
    from repro.kernels.lanczos_reorth import a_block_index
    b_dim = 3
    index = a_block_index(side == "right", f)
    seen, fetches = None, 0
    for b in range(b_dim):
        for p in range(3):
            for j in range(f):
                blk = tuple(int(i) for i in index(b, p, j))
                assert blk[0] == b
                if p == 0:
                    assert blk[1:] == ((0, j) if side == "right" else (j, 0))
                fetches += blk != seen
                seen = blk
    assert fetches == b_dim * f


@pytest.mark.parametrize("f", [2, 4])
@pytest.mark.parametrize("side", ["right", "left"])
def test_reorth_batched_matches_reference(side, f):
    """B = 3 in one interpret-mode launch == the jnp CGS2 reference per
    batch element, and bit-for-bit what the same kernel gives when A's
    block index follows j in every pass (A re-fetched in passes 1–2)."""
    from repro.kernels import lanczos_reorth as LR
    b, s, h, k = 3, 64, 128, 8
    right = side == "right"
    n = h if right else s
    keys = jax.random.split(jax.random.PRNGKey(70), 2 + b)
    a = _mk(keys[0], (b, s, h), jnp.float32)
    x = _mk(keys[1], (b, s if right else h), jnp.float32)
    q = jnp.stack([jnp.linalg.qr(_mk(keys[2 + i], (n, k), jnp.float32))[0]
                   for i in range(b)])
    z, nrm = LR._launch(a, x, q, right=right, expansion=f, interpret=True)
    oracle = ref.reorth_right if right else ref.reorth_left
    for i in range(b):
        z_ref, nrm_ref = oracle(a[i], x[i], q[i])
        np.testing.assert_allclose(np.asarray(z[i]), np.asarray(z_ref),
                                   rtol=1e-4, atol=1e-2)
        np.testing.assert_allclose(float(nrm[i]), float(nrm_ref), rtol=1e-4)

    def every_pass(right, f):
        return lambda bi, p, j: (bi, 0, j) if right else (bi, j, 0)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(LR, "a_block_index", every_pass)
        z_all, nrm_all = LR._launch(a, x, q, right=right, expansion=f,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(z), np.asarray(z_all))
    np.testing.assert_array_equal(np.asarray(nrm), np.asarray(nrm_all))


def _reorth_case(side, b=3, s=64, h=256, k=8, seed=80):
    """A [b, s, h] of bf16 values held in f32, the f32 vector x of the
    step and an orthonormal Q [b, n, k] per batch element."""
    right = side == "right"
    n = h if right else s
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 + b)
    a = _mk(keys[0], (b, s, h), jnp.bfloat16).astype(jnp.float32)
    x = _mk(keys[1], (b, s if right else h), jnp.float32)
    q = jnp.stack([jnp.linalg.qr(_mk(keys[2 + i], (n, k), jnp.float32))[0]
                   for i in range(b)])
    return a, x, q


@pytest.mark.parametrize("f", [1, 4, 8])
@pytest.mark.parametrize("side", ["right", "left"])
def test_reorth_bf16_a_matches_f32_path(side, f):
    """A bf16 A takes pass 0's one-pass split product; on the same values
    held in f32 the HIGHEST path computes the same terms, so z and ‖z‖²
    agree to f32 rounding (1e-6 relative), not to bf16's."""
    from repro.kernels import lanczos_reorth as LR
    a32, x, q = _reorth_case(side)
    fn = LR.reorth_right_batched if side == "right" \
        else LR.reorth_left_batched
    z16, n16 = fn(a32.astype(jnp.bfloat16), x, q, expansion=f,
                  interpret=True)
    z32, n32 = fn(a32, x, q, expansion=f, interpret=True)
    z16, n16, z32, n32 = map(np.asarray, (z16, n16, z32, n32))
    assert np.abs(z16 - z32).max() <= 1e-6 * np.abs(z32).max()
    np.testing.assert_allclose(n16, n32, rtol=1e-6, atol=0)


def _f32_only_kernel(a_ref, x_ref, q_ref, z_ref, nrm_ref, p1, p2, *,
                     right: bool):
    """The re-orth kernel body before bf16 A had a pass 0 of its own:
    every A cast to f32 and every product at HIGHEST (the frozen oracle
    for the f32 path)."""
    from jax.experimental import pallas as pl
    from repro.kernels.lanczos_reorth import _row_dot, _row_dot_t
    p = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((p == 0) & (j == 0))
    def _init():
        p1[...] = jnp.zeros_like(p1)
        p2[...] = jnp.zeros_like(p2)
        nrm_ref[...] = jnp.zeros_like(nrm_ref)

    q = q_ref[0].astype(jnp.float32)

    @pl.when(p == 0)
    def _pass0():
        a = a_ref[0].astype(jnp.float32)
        x = x_ref[0].astype(jnp.float32)
        z = _row_dot(x, a) if right else _row_dot_t(x, a)
        z_ref[0, j] = z
        p1[...] += _row_dot(z, q)

    @pl.when(p == 1)
    def _pass1():
        z = z_ref[0, j] - _row_dot_t(p1[...], q)
        z_ref[0, j] = z
        p2[...] += _row_dot(z, q)

    @pl.when(p == 2)
    def _pass2():
        z = z_ref[0, j] - _row_dot_t(p2[...], q)
        z_ref[0, j] = z
        nrm_ref[0] += jnp.sum(z * z, axis=1, keepdims=True)


@pytest.mark.parametrize("f", [1, 4])
@pytest.mark.parametrize("side", ["right", "left"])
def test_reorth_f32_a_keeps_highest_path(side, f):
    """An f32 A (the outlier track's base, any f32 caller) takes no split:
    every product in the kernel is f32 at HIGHEST, and z and ‖z‖² are
    bit-for-bit what the f32-only kernel body gives."""
    from repro.kernels import lanczos_reorth as LR
    a, x, q = _reorth_case(side)
    right = side == "right"
    jaxpr = jax.make_jaxpr(lambda a, x, q: LR._launch(
        a, x, q, right=right, expansion=f, interpret=True))(a, x, q)
    body = str(next(e for e in jaxpr.eqns
                    if e.primitive.name == "pallas_call").params["jaxpr"])
    assert "bf16" not in body
    assert body.count("dot_general") == \
        body.count("precision=(Precision.HIGHEST, Precision.HIGHEST)") == 5
    z, nrm = LR._launch(a, x, q, right=right, expansion=f, interpret=True)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(LR, "_reorth_kernel", _f32_only_kernel)
        z_old, nrm_old = LR._launch(a, x, q, right=right, expansion=f,
                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(z), np.asarray(z_old))
    np.testing.assert_array_equal(np.asarray(nrm), np.asarray(nrm_old))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("k", [8, 16])
@pytest.mark.parametrize("f", [4, 8])
def test_reorth_right(shape, k, f):
    s, h = shape
    a = _mk(jax.random.PRNGKey(4), (s, h), jnp.float32)
    u = _mk(jax.random.PRNGKey(5), (s,), jnp.float32)
    q = jnp.linalg.qr(_mk(jax.random.PRNGKey(6), (h, k), jnp.float32))[0]
    z, n2 = ops.reorth_right(a, u, q, expansion=f)
    z_ref, n2_ref = ref.reorth_right(a, u, q)
    np.testing.assert_allclose(z, z_ref, rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(n2, n2_ref, rtol=1e-4)
    # the defining property: output orthogonal to the Q columns
    assert float(jnp.abs(q.T @ z).max()) < 1e-3


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("f", [4, 8])
def test_reorth_left(shape, f):
    s, h = shape
    a = _mk(jax.random.PRNGKey(7), (s, h), jnp.float32)
    v = _mk(jax.random.PRNGKey(8), (h,), jnp.float32)
    q = jnp.linalg.qr(_mk(jax.random.PRNGKey(9), (s, 12), jnp.float32))[0]
    z, n2 = ops.reorth_left(a, v, q, expansion=f)
    z_ref, n2_ref = ref.reorth_left(a, v, q)
    np.testing.assert_allclose(z, z_ref, rtol=1e-4, atol=1e-2)
    assert float(jnp.abs(q.T @ z).max()) < 1e-3


@pytest.mark.parametrize("k", [4, 10, 16])
@pytest.mark.parametrize("n", [128, 384])
@pytest.mark.parametrize("f", [4, 8])
def test_lowrank_matmul(k, n, f):
    vt = _mk(jax.random.PRNGKey(10), (k, 512), jnp.float32)
    w = _mk(jax.random.PRNGKey(11), (512, n), jnp.float32) * 0.1
    np.testing.assert_allclose(ops.lowrank_matmul(vt, w, expansion=f),
                               ref.lowrank_matmul(vt, w),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("t", [0.5, 1.5, 3.0])
def test_outlier_stats(shape, t):
    a = _mk(jax.random.PRNGKey(12), shape, jnp.float32)
    cnt, mx = ops.outlier_stats(a, t, expansion=4)
    cnt_ref, mx_ref = ref.outlier_stats(a, t)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_ref))
    np.testing.assert_allclose(mx, mx_ref, rtol=1e-6)


def test_pallas_hooks_full_lanczos():
    """End-to-end: Lanczos with Pallas fused steps == jnp reference."""
    from repro.core import lanczos_svd
    a = jax.random.normal(jax.random.PRNGKey(13), (128, 8)) @ \
        jax.random.normal(jax.random.PRNGKey(14), (8, 256))
    hooks = ops.make_pallas_hooks(expansion=8)
    u1, s1, v1 = lanczos_svd(a, rank=8, iters=12, hooks=hooks)
    u2, s2, v2 = lanczos_svd(a, rank=8, iters=12)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-3)
    rec = (u1 * s1) @ v1
    assert float(jnp.linalg.norm(rec - a) / jnp.linalg.norm(a)) < 1e-3


@pytest.mark.parametrize("t", [128, 512])
@pytest.mark.parametrize("g,r", [(4, 16), (8, 32)])
@pytest.mark.parametrize("f", [4, 8])
def test_dkv_attention_stats(t, g, r, f):
    """Rank-space flash stats == full-score oracle."""
    inner = _mk(jax.random.PRNGKey(20), (g, r), jnp.float32)
    k_u = _mk(jax.random.PRNGKey(21), (t, r), jnp.float32)
    v_u = _mk(jax.random.PRNGKey(22), (t, r), jnp.float32)
    a, m, l = ops.dkv_attention_stats(inner, k_u, v_u, expansion=f)
    a_r, m_r, l_r = ref.dkv_attention_stats(inner, k_u, v_u)
    np.testing.assert_allclose(m, m_r, rtol=1e-5)
    np.testing.assert_allclose(l, l_r, rtol=1e-4)
    np.testing.assert_allclose(a, a_r, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("t", [1, 7, 100, 130])
@pytest.mark.parametrize("f", [4, 8])
def test_dkv_attention_stats_arbitrary_length(t, f):
    """Non-divisible cache lengths (incl. t < f, where whole grid blocks
    are padding): the wrapper pads the time axis through the cached pad
    plan and the kernel masks pad rows out of the softmax EXACTLY."""
    g, r = 4, 16
    inner = _mk(jax.random.PRNGKey(30), (g, r), jnp.float32)
    k_u = _mk(jax.random.PRNGKey(31), (t, r), jnp.float32)
    v_u = _mk(jax.random.PRNGKey(32), (t, r), jnp.float32)
    a, m, l = ops.dkv_attention_stats(inner, k_u, v_u, expansion=f)
    a_r, m_r, l_r = ref.dkv_attention_stats(inner, k_u, v_u)
    np.testing.assert_allclose(m, m_r, rtol=1e-5)
    np.testing.assert_allclose(l, l_r, rtol=1e-4)
    np.testing.assert_allclose(a, a_r, rtol=1e-4, atol=1e-3)


def test_dkv_attention_stats_padding_is_bit_exact():
    """Padded launch (t=96+pad at f=8 → 96 divisible; compare t=90) must
    equal slicing a longer divisible launch's inputs — the masked rows
    contribute literal zeros, not epsilon."""
    g, r, f = 4, 8, 8
    inner = _mk(jax.random.PRNGKey(33), (g, r), jnp.float32)
    k_u = _mk(jax.random.PRNGKey(34), (96, r), jnp.float32)
    v_u = _mk(jax.random.PRNGKey(35), (96, r), jnp.float32)
    # oracle on the 90-row prefix, computed WITHOUT padding (f=1 divides)
    a1, m1, l1 = ops.dkv_attention_stats(inner, k_u[:90], v_u[:90],
                                         expansion=1)
    a8, m8, l8 = ops.dkv_attention_stats(inner, k_u[:90], v_u[:90],
                                         expansion=f)
    np.testing.assert_allclose(np.asarray(m8), np.asarray(m1), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(l8), np.asarray(l1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(a8), np.asarray(a1),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("perm_seed,t_valid", [(0, 32), (1, 27), (2, 9)])
def test_dkv_attention_stats_paged_matches_contiguous(perm_seed, t_valid):
    """Paged stats (blocks DMA'd by prefetched page id through the block
    table) are BIT-IDENTICAL to the contiguous kernel run on the gathered
    rows at expansion == n_pages: same block partitioning, same online-
    softmax math — only the addressing differs.  Covers permuted page
    order and a partially filled last page (t_valid < n·page)."""
    P, page, g, r, n = 12, 8, 4, 16, 4
    rng = np.random.RandomState(50 + perm_seed)
    pools_k = jnp.asarray(rng.randn(P, page, r).astype(np.float32))
    pools_v = jnp.asarray(rng.randn(P, page, r).astype(np.float32))
    inner = jnp.asarray(rng.randn(g, r).astype(np.float32))
    ids = jnp.asarray(rng.permutation(np.arange(1, P))[:n].astype(np.int32))
    a_p, m_p, l_p = ops.dkv_attention_stats_paged(
        inner, pools_k, pools_v, ids, t_valid=t_valid)
    from repro.kernels import dkv_attention as _dkv
    gath_k = pools_k[ids].reshape(-1, r)
    gath_v = pools_v[ids].reshape(-1, r)
    a_c, m_c, l_c = _dkv.dkv_attention_stats(inner, gath_k, gath_v,
                                             expansion=n, t_valid=t_valid,
                                             interpret=True)
    assert (np.asarray(a_p) == np.asarray(a_c)).all()
    assert (np.asarray(m_p) == np.asarray(m_c)).all()
    assert (np.asarray(l_p) == np.asarray(l_c)).all()


def test_dkv_merge_with_tail_exact():
    """Kernel stats + dense-tail merge == softmax over the full sequence."""
    g, r, t, tl, d = 4, 8, 256, 16, 32
    inner = _mk(jax.random.PRNGKey(23), (g, r), jnp.float32)
    k_u = _mk(jax.random.PRNGKey(24), (t, r), jnp.float32)
    v_u = _mk(jax.random.PRNGKey(25), (t, r), jnp.float32)
    v_vt = _mk(jax.random.PRNGKey(26), (r, d), jnp.float32)
    tail_sc = _mk(jax.random.PRNGKey(27), (g, tl), jnp.float32)
    tail_v = _mk(jax.random.PRNGKey(28), (tl, d), jnp.float32)

    a, m, l = ops.dkv_attention_stats(inner, k_u, v_u, expansion=8)
    out = ops.merge_with_tail(a, m, l, v_vt, tail_sc, tail_v)

    # oracle: one softmax over [prefix scores | tail scores]
    s_pre = inner @ k_u.T
    s_all = jnp.concatenate([s_pre, tail_sc], axis=1)
    p_all = jax.nn.softmax(s_all, axis=1)
    v_pre = v_u @ v_vt                    # [t, d] reconstructed prefix V
    v_all = jnp.concatenate([v_pre, tail_v], axis=0)
    out_ref = p_all @ v_all
    np.testing.assert_allclose(out, out_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("q,nh,hd", [(16, 4, 8), (32, 8, 16), (64, 4, 32)])
@pytest.mark.parametrize("hb", [2, 4])
def test_ssd_chunk_intra(q, nh, hd, hb):
    """Fused intra-chunk SSD == materialized masked-decay oracle."""
    g = 3
    cb = _mk(jax.random.PRNGKey(30), (g, q, q), jnp.float32) * 0.3
    # log-decay must be non-increasing along the chunk (cumsum of negatives)
    da = -jnp.abs(_mk(jax.random.PRNGKey(31), (g, q, nh), jnp.float32)) * 0.05
    l = jnp.cumsum(da, axis=1)
    dt = jnp.abs(_mk(jax.random.PRNGKey(32), (g, q, nh), jnp.float32))
    x = _mk(jax.random.PRNGKey(33), (g, q, nh, hd), jnp.float32)
    got = ops.ssd_chunk_intra(cb, l, dt, x, head_block=hb)
    want = ref.ssd_chunk_intra(cb, l, dt, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_ssd_chunk_matches_model_math():
    """The kernel reproduces mamba2.ssd_apply's intra-chunk term exactly."""
    q, nh, hd, ds = 16, 4, 8, 8
    g = 2
    key = jax.random.PRNGKey(40)
    cm = jax.random.normal(key, (g, q, ds))
    bm = jax.random.normal(jax.random.PRNGKey(41), (g, q, ds))
    cb = jnp.einsum("gqd,gsd->gqs", cm, bm)
    da = -jnp.abs(jax.random.normal(jax.random.PRNGKey(42), (g, q, nh))) * 0.1
    l = jnp.cumsum(da, axis=1)
    dt = jnp.abs(jax.random.normal(jax.random.PRNGKey(43), (g, q, nh)))
    xh = jax.random.normal(jax.random.PRNGKey(44), (g, q, nh, hd))
    # model formulation (mamba2.ssd_apply intra-chunk lines)
    decay = jnp.exp(l[:, :, None, :] - l[:, None, :, :])
    mask = jnp.tril(jnp.ones((q, q), bool))
    m = cb[..., None] * jnp.where(mask[None, :, :, None], decay, 0.0) \
        * dt[:, None, :, :]
    y_model = jnp.einsum("gqsn,gsnd->gqnd", m, xh)
    y_kernel = ops.ssd_chunk_intra(cb, l, dt, xh, head_block=4)
    np.testing.assert_allclose(y_kernel, y_model, rtol=1e-4, atol=1e-4)
