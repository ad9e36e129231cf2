"""Fused Lanczos re-orthogonalization step — the D-com kernel (paper §5.3).

The latency bottleneck of Lanczos bidiagonalization is the inner-loop
re-orthogonalization (paper Fig. 3): a chain of

    matvec  →  global reduce (Qᵀz)  →  broadcast  →  axpy (z − Q·p)   × 2

which is memory-bound on a GPU/TPU vector unit.  The paper's *Computation
Expansion* replicates the element-wise work across ``f`` partial blocks so
the one long global reduction becomes ``f`` short local reductions plus a
tiny global combine (Fig. 9c).

TPU-native mapping (see DESIGN.md §2): the expansion factor ``f`` is the
Pallas **grid size along the output dimension**.  Each grid step owns a
VMEM-resident tile (the paper's per-cluster buffer) and computes

  pass 0:  z_j   = (Aᵀu)_j            and accumulates p1 += Q_jᵀ z_j
  pass 1:  z'_j  = z_j − Q_j p1       and accumulates p2 += Q_jᵀ z'_j
  pass 2:  z''_j = z'_j − Q_j p2      and accumulates ‖z''‖² partials

The p1/p2 accumulators are tiny [1, k] VMEM scratch — the paper's "small
global memory for broadcast purposes".  z lives in the output block, which
stays resident in VMEM across all 3·f steps of one batch element (its block
index only moves with the batch), so the intermediate never round-trips
through HBM.  Only pass 0 reads A, so A's block index (:func:`a_block_index`)
walks the f blocks in pass 0 and then holds at the last one: the pipeline
copies a block only when its index changes, so each A block moves HBM→VMEM
once per batch element, and block f−1 stays resident through passes 1–2.
Q is read in every pass and is fetched in each.

Two symmetric variants share one kernel body:
* ``right``: z = CGS2(Aᵀu, V) — output over columns of A (length H),
* ``left`` : w = CGS2(A v, U) — output over rows of A (length S).

Layout for the TPU compiler (Mosaic): every vector is a lane-major row
[1, n], and z is stored as [f, 1, blk] so each grid step addresses its
block by a leading-dim index.  Products run on the MXU, by A's dtype:

* float32 A: every product at ``Precision.HIGHEST``.
* bfloat16 A (the K/V prefill wrote, streamed as written): pass 0 splits
  the f32 vector x exactly into three bf16 parts, x1 = bf16(x),
  x2 = bf16(x − x1), x3 = bf16(x − x1 − x2), stacks them as rows 0–2 of
  an [8, n] left operand (rows 3–7 zero), makes ONE bf16 MXU product with
  f32 accumulation, and sums the output rows smallest first,
  (row 2 + row 1) + row 0.  Every bf16 × bf16 product is exact in f32,
  so this makes the terms ``HIGHEST`` makes on the same A less those that
  multiply A's low parts, which are zero for a bf16-valued A: the same
  arithmetic in one pass instead of six, over half the bytes.  Passes 1–2,
  Q, z, p1/p2 and the norms stay f32 at ``HIGHEST``.

Compiled launches need the A block to tile: ``blk = H / f`` a multiple of
128 lanes (:data:`LANE`) on the right step, ``blk = S / f`` a multiple of
8 sublanes (:data:`SUBLANE`) on the left; ``kernels.ops.padded_dims``
pads S and H accordingly.  Interpret mode accepts any divisor.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..engine.platform import resolve_interpret

#: Lanes / sublanes of a TPU vector register: the compiled A block is a
#: whole number of (SUBLANE, LANE) tiles.
LANE = 128
SUBLANE = 8
#: Scoped-VMEM ceiling the compiled kernel may request (v5e has 128 MiB).
_VMEM_CAP = 100 * 2 ** 20
_VMEM_DEFAULT = 16 * 2 ** 20

_HI = jax.lax.Precision.HIGHEST


def _row_dot(x, y):
    """[1, n] · [n, m] → [1, m] at full f32 precision."""
    return jnp.dot(x, y, precision=_HI, preferred_element_type=jnp.float32)


def _row_dot_t(x, y):
    """[1, n] · [m, n]ᵀ → [1, m] at full f32 precision."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _split3(x):
    """f32 [1, n] → bf16 [8, n]: rows 0–2 hold x1 = bf16(x),
    x2 = bf16(x − x1), x3 = bf16(x − x1 − x2), whose sum is x exactly;
    rows 3–7 are zero (a whole sublane tile for the MXU)."""
    x1 = x.astype(jnp.bfloat16).astype(jnp.float32)
    r = x - x1
    x2 = r.astype(jnp.bfloat16).astype(jnp.float32)
    x3 = (r - x2).astype(jnp.bfloat16).astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, x.shape[1]), 0)
    parts = jnp.where(row == 0, x1,
                      jnp.where(row == 1, x2, jnp.where(row == 2, x3, 0.0)))
    return parts.astype(jnp.bfloat16)


def _split_dot(x, a, right: bool):
    """x · A (right) or x · Aᵀ (left) for a bf16 A: one bf16 MXU product
    of x's exact three-part split, f32 accumulation, rows summed smallest
    first → (1, blk)."""
    parts = _split3(x)
    if right:
        out = jnp.dot(parts, a, preferred_element_type=jnp.float32)
    else:
        out = jax.lax.dot_general(parts, a, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    return (out[2:3] + out[1:2]) + out[0:1]


def _reorth_kernel(a_ref, x_ref, q_ref, z_ref, nrm_ref, p1, p2, *,
                   right: bool):
    """grid = (B, 3 passes, f blocks) — batch is the OUTERMOST grid dim, so
    one launch covers every prompt and p1/p2 are re-initialized as each
    batch element's pass 0 begins.  A block: (S, blk) right / (blk, H) left;
    x: u (1, S) right / v (1, H) left; Q block (blk, k)."""
    p = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((p == 0) & (j == 0))
    def _init():
        p1[...] = jnp.zeros_like(p1)
        p2[...] = jnp.zeros_like(p2)
        nrm_ref[...] = jnp.zeros_like(nrm_ref)

    q = q_ref[0].astype(jnp.float32)                       # (blk, k)

    @pl.when(p == 0)
    def _pass0():
        x = x_ref[0].astype(jnp.float32)
        if a_ref.dtype == jnp.bfloat16:
            z = _split_dot(x, a_ref[0], right)             # (1, blk)
        else:
            a = a_ref[0].astype(jnp.float32)
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


def _vmem_limit(a_bytes: int, x_len: int, q_blk: int, k: int, out_len: int
                ) -> int:
    """Scoped VMEM for one launch: double-buffered A block (``a_bytes`` at
    A's itemsize) and f32 x/Q/z blocks (rows pad to 8 sublanes, k pads to
    whole lanes) plus headroom."""
    k_pad = -(-k // LANE) * LANE
    need = 2 * (a_bytes + 4 * (8 * x_len + q_blk * k_pad + 8 * out_len))
    need = need + need // 4 + 2 * 2 ** 20
    if need > _VMEM_CAP:
        raise ValueError(f"re-orth launch needs {need} B of VMEM "
                         f"(cap {_VMEM_CAP}); raise the expansion factor")
    return max(need, _VMEM_DEFAULT)


def a_block_index(right: bool, f: int):
    """A's index map over the grid (b, p, j): block j in pass 0, then block
    f−1 (the one pass 0 leaves resident) in passes 1–2, which never read A,
    so the pipeline issues no copy of A after pass 0."""
    last = f - 1

    def index(b, p, j):
        blk = jnp.where(p == 0, j, last)
        return (b, 0, blk) if right else (b, blk, 0)

    return index


def _launch(a, x, q, *, right: bool, expansion: int, interpret: bool):
    """One pallas_call over the batch: returns (z [B, n], ‖z‖² [B])."""
    b_dim, s_dim, h_dim = a.shape
    k = q.shape[-1]
    n = h_dim if right else s_dim
    f = expansion
    if n % f:
        raise ValueError(f"reduced axis {n} must divide expansion {f}")
    blk = n // f
    tile = LANE if right else SUBLANE
    if not interpret and f > 1 and blk % tile:
        raise ValueError(f"compiled re-orth needs {n}/{f} = {blk} to be a "
                         f"multiple of {tile}; pad through "
                         "kernels.ops.padded_dims")
    a_blk = (1, s_dim, blk) if right else (1, blk, h_dim)
    a_spec = pl.BlockSpec(a_blk, a_block_index(right, f))
    x_len = s_dim if right else h_dim
    params = None if interpret else pltpu.CompilerParams(
        vmem_limit_bytes=_vmem_limit(s_dim * h_dim // f * a.dtype.itemsize,
                                     x_len, blk, k, n))
    z, nrm = pl.pallas_call(
        functools.partial(_reorth_kernel, right=right),
        grid=(b_dim, 3, f),
        in_specs=[
            a_spec,
            pl.BlockSpec((1, 1, x_len), lambda b, p, j: (b, 0, 0)),
            pl.BlockSpec((1, blk, k), lambda b, p, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, f, 1, blk), lambda b, p, j: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, p, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_dim, f, 1, blk), jnp.float32),
            jax.ShapeDtypeStruct((b_dim, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.float32),                # p1 = Qᵀz
            pltpu.VMEM((1, k), jnp.float32),                # p2
        ],
        compiler_params=params,
        interpret=interpret,
    )(a, x[:, None, :], q)
    return z.reshape(b_dim, n), nrm[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("expansion", "interpret"))
def reorth_right_batched(a: jax.Array, u: jax.Array, v_buf: jax.Array,
                         *, expansion: int = 8,
                         interpret: Optional[bool] = None):
    """Batched fused  z_b = CGS2(A_bᵀ·u_b, V_b)  → (z [B, H], ‖z‖² [B]).

    ONE pallas_call for the whole batch: grid (B, 3, f).  H must divide by
    ``expansion`` (and by 128·f when compiled).
    """
    return _launch(a, u, v_buf, right=True, expansion=expansion,
                   interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("expansion", "interpret"))
def reorth_left_batched(a: jax.Array, v: jax.Array, u_buf: jax.Array,
                        *, expansion: int = 8,
                        interpret: Optional[bool] = None):
    """Batched fused  w_b = CGS2(A_b·v_b, U_b)  → (w [B, S], ‖w‖² [B]).

    S must divide by ``expansion`` (and by 8·f when compiled)."""
    return _launch(a, v, u_buf, right=False, expansion=expansion,
                   interpret=resolve_interpret(interpret))


def reorth_right(a: jax.Array, u: jax.Array, v_buf: jax.Array,
                 *, expansion: int = 8, interpret: Optional[bool] = None):
    """Fused  z = CGS2(Aᵀ·u, V)  → (z [H], ‖z‖² scalar): the B=1 slice of
    :func:`reorth_right_batched`."""
    z, nrm = reorth_right_batched(a[None], u[None], v_buf[None],
                                  expansion=expansion, interpret=interpret)
    return z[0], nrm[0]


def reorth_left(a: jax.Array, v: jax.Array, u_buf: jax.Array,
                *, expansion: int = 8, interpret: Optional[bool] = None):
    """Fused  w = CGS2(A·v, U)  → (w [S], ‖w‖² scalar): the B=1 slice of
    :func:`reorth_left_batched`."""
    w, nrm = reorth_left_batched(a[None], v[None], u_buf[None],
                                 expansion=expansion, interpret=interpret)
    return w[0], nrm[0]


# -- tunable space (see repro.tune): the decomposition operating point ------
# ``backend`` selects the execution substrate (engine.backends registry);
# ``reorth`` declares the re-orthogonalization cadence — CGS2 is the only
# implemented point today, registered so the axis is tunable the day a
# cheaper cadence lands.
from ..tune.space import (EXPANSION_GRID, TunableParam,  # noqa: E402
                          TunableSpace, register_space)

register_space(TunableSpace("lanczos_reorth", (
    TunableParam("expansion", EXPANSION_GRID, default=8),
    TunableParam("backend", ("reference", "pallas_interpret", "pallas",
                             "pallas_vmap"), default="reference"),
    TunableParam("reorth", ("cgs2",), default="cgs2"),
)))
