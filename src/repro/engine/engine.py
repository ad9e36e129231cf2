"""DecomposeEngine — the one owner of the activation-decomposition pipeline.

Every consumer (``models/decomposed*.py``, ``runtime/steps.py``,
``serving``, ``launch/serve.py``) constructs ONE engine from an
:class:`~repro.engine.config.EngineConfig` and obtains decomposition
exclusively through it.  The engine owns, end to end:

1. **Backend dispatch** — jnp reference / Pallas interpret / Pallas
   compiled / vmap fallback, selected once at construction (never per op).
2. **Batched Lanczos** — ``decompose`` runs the natively batched pipeline:
   one fused kernel launch per Lanczos pass for the whole [B, S, H] batch.
3. **Shape plumbing** — kernel backends need the reduced axes to divide the
   expansion factor; the engine pads through the cached plan in
   ``kernels.ops`` (``padded_dims``/``pad_plan``) and slices factors back.
   The start vector is zero-padded, so pad rows/columns stay EXACTLY zero
   through every iteration — padded and unpadded runs are the same math.
4. **Multi-track outliers** — ``decompose_activation`` applies the per-layer
   policy (rank, iters, outlier fraction, calibrated threshold) before the
   base-track Lanczos and re-attaches the dense outlier track (paper §4).
5. **Preserved consumption** — Eq. 6/7 projections and the factored
   attention contractions (paper §3.2) are exposed as engine methods so the
   consumption side of the pipeline rides the same object.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import lanczos as lz
from ..core import outlier as ol
from ..core.lowrank import LowRank, add_bias_rank, from_dense_svd
from ..core.policy import LayerPolicy
from ..core.preserved import (decompose_weight, lowrank_matmul,
                              lowrank_x_lowrank_weight, preserved_pv,
                              preserved_qk_scores)
from .backends import Backend, get_backend
from .config import EngineConfig

Array = jax.Array


@functools.lru_cache(maxsize=None)
def _padded_z0(h_dim: int, h_pad: int) -> np.ndarray:
    """Fixed start direction of the UNPADDED width, zero-extended: pad
    components then stay exactly zero through every re-orth step, so all
    backends (padded or not) run the same arithmetic.  Cached per width so
    the per-layer hot path doesn't re-dispatch the eager normal+pad; the
    value is identical to the default the jitted core generates (same key,
    same shape, deterministic threefry).

    The cache holds the HOST-side numpy value, never a committed device
    array: jit places it per call site, so the same entry serves every
    device/mesh and the cache cannot pin stale device buffers (it used to
    hold device arrays keyed only on widths — wrong device under a mesh
    and a per-width buffer leak)."""
    with jax.ensure_compile_time_eval():     # concrete even under a trace
        z0 = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (h_dim,),
                                          jnp.float32))
    return np.pad(z0, (0, h_pad - h_dim))


@functools.lru_cache(maxsize=None)
def _sharded_decompose(mesh, batch_spec: P, rank: int, iters: int, hooks,
                       use_shard_map: bool):
    """Jitted Lanczos pipeline with EXPLICIT in/out shardings on ``mesh``.

    ``batch_spec`` shards the flat [B, S, H] batch axis over the mesh's DP
    super-axis (P() = replication fallback when B doesn't divide).  Two
    lowerings, same math:

    * plain jit + in/out shardings — GSPMD partitions the batched einsum
      steps (reference backend; every op is batch-parallel so no
      collectives appear),
    * ``shard_map`` over DP — each device runs the decomposition on ITS
      batch shard with a device-local Pallas grid (kernel backends: the
      grid is sized by the LOCAL batch, which a global-view lowering
      cannot express).

    Cached per (mesh, spec, rank, iters, hooks, lowering) so serving's
    per-prefill hot path reuses one executable.
    """
    dp = batch_spec[0] if len(batch_spec) else None

    def run(xf: Array, z0: Array):
        return lz.decompose(xf, rank, iters=iters, batched_hooks=hooks,
                            z0=z0)

    if use_shard_map and dp is not None:
        in_specs = (P(dp, None, None), P())
        out_specs = LowRank(P(dp, None, None), P(dp, None), P(dp, None, None))
        return jax.jit(jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))
    x_sh = NamedSharding(mesh, P(dp, None, None))
    z_sh = NamedSharding(mesh, P())
    out_sh = LowRank(NamedSharding(mesh, P(dp, None, None)),
                     NamedSharding(mesh, P(dp, None)),
                     NamedSharding(mesh, P(dp, None, None)))
    return jax.jit(run, in_shardings=(x_sh, z_sh), out_shardings=out_sh)


class DecomposeEngine:
    """Single entry point for every decomposition in the system."""

    def __init__(self, config: EngineConfig):
        self.config = config
        backend_name = config.backend
        if backend_name == "auto":
            # tuner-resolved at build: measured cache override when
            # benchmarks/run.py --tune ran on this machine, else the
            # platform heuristic (Mosaic on TPU, jnp reference on CPU)
            from .. import tune
            backend_name = tune.resolve_backend()
        self.backend: Backend = get_backend(backend_name)
        self._auto_expansion = config.expansion == "auto"
        # Hooks resolved ONCE for a fixed f; factories are lru-cached
        # upstream so the returned functions hash stably as static jit
        # arguments.  With expansion="auto" the f — and therefore the
        # hooks — resolve per shape-bucket at decompose time through the
        # tuner's in-process lru (same cached factories, same identities
        # as a fixed-f engine at that f).
        self._hooks = None if self._auto_expansion \
            else self.backend.make_hooks(config.expansion)

    # -- config passthroughs ---------------------------------------------
    def layer_policy(self, idx: int) -> LayerPolicy:
        return self.config.layer(idx)

    def threshold(self, idx: int) -> float:
        return self.config.threshold(idx)

    @property
    def attn_mode(self) -> str:
        return self.config.attn_mode

    @property
    def resolved_backend(self) -> str:
        """The registry key actually in use (``"auto"`` resolved)."""
        return self.backend.name

    def resolve_expansion(self, s_dim: int, h_dim: int, batch: int = 1,
                          dtype: object = "float32") -> int:
        """The expansion factor f this engine runs a [batch, S, H]
        decomposition at: the configured int, or — for ``"auto"`` — the
        ``repro.tune`` answer for this shape-bucket (cache hit / cost
        model; in-process lru, so the per-layer hot path is a dict
        lookup)."""
        if not self._auto_expansion:
            return self.config.expansion
        from .. import tune
        return tune.tuned_expansion((int(batch), int(s_dim), int(h_dim)),
                                    dtype=str(dtype),
                                    backend=self.backend.name)

    # -- stage 1: batched Lanczos decomposition ---------------------------
    def decompose(self, x: Array, rank: int,
                  iters: Optional[int] = None) -> LowRank:
        """x [..., S, H] → LowRank via the engine's backend.

        One natively batched Lanczos run; kernel backends get zero-padding
        to the cached (S_pad, H_pad) plan and exact slice-back.
        """
        from ..kernels import ops
        s_dim, h_dim = x.shape[-2:]
        batch = 1
        for d in x.shape[:-2]:
            batch *= int(d)
        f = self.resolve_expansion(s_dim, h_dim, max(1, batch), x.dtype)
        hooks = self._hooks if self._hooks is not None \
            else self.backend.make_hooks(f)
        pad = self.backend.requires_padding
        if pad:
            s_pad, h_pad = ops.padded_dims(s_dim, h_dim, f)
            pad = (s_pad, h_pad) != (s_dim, h_dim)
        if pad:
            widths = [(0, 0)] * (x.ndim - 2) + \
                [(0, s_pad - s_dim), (0, h_pad - h_dim)]
            xp = jnp.pad(x, widths)
            # zero-extended start vector keeps pad rows/cols exactly zero,
            # so padded and unpadded runs are the same arithmetic
            z0 = _padded_z0(h_dim, h_pad)
        else:
            xp, z0 = x, None        # jitted core generates the same z0
        if self.config.mesh is not None:
            lr = self._decompose_sharded(xp, rank, iters, hooks, z0)
        else:
            lr = lz.decompose(xp, rank, iters=iters,
                              batched_hooks=hooks, z0=z0)
        if pad:
            lr = LowRank(lr.u[..., :s_dim, :], lr.core,
                         lr.vt[..., :h_dim])
        return lr

    def _decompose_sharded(self, xp: Array, rank: int,
                           iters: Optional[int], hooks, z0) -> LowRank:
        """Run the batched Lanczos pipeline DP-sharded over ``config.mesh``.

        The flat batch axis shards over the DP super-axis when it divides
        (replication fallback otherwise — the same divisibility guard as
        every rule in ``distributed.sharding``).  The per-element math is
        identical to the unsharded path: the explicit ``z0`` equals the
        default the jitted core generates, every op is batch-parallel, and
        kernel backends go through ``shard_map`` so each device launches a
        grid over its LOCAL batch shard.
        """
        from ..distributed import sharding as sh
        mesh = self.config.mesh
        iters = rank if iters is None else iters
        batch_shape = xp.shape[:-2]
        flat = xp.reshape((-1,) + xp.shape[-2:])
        if z0 is None:
            # same key/shape as the jitted core's default → same values
            z0 = _padded_z0(flat.shape[-1], flat.shape[-1])
        dp_sz = sh.axis_size(mesh, sh.dp_axes(mesh))
        shard = flat.shape[0] % dp_sz == 0 and flat.shape[0] > 0
        spec = P(sh.dp_name(mesh)) if shard else P()
        fn = _sharded_decompose(mesh, spec, rank, iters, hooks,
                                self.backend.requires_padding)
        lr = fn(flat, np.asarray(z0, np.float32))
        return LowRank(lr.u.reshape(batch_shape + lr.u.shape[1:]),
                       lr.core.reshape(batch_shape + lr.core.shape[1:]),
                       lr.vt.reshape(batch_shape + lr.vt.shape[1:]))

    # -- stage 2: policy-driven multi-track activation decomposition ------
    def decompose_activation(self, x: Array, layer_idx: Optional[int] = None,
                             lp: Optional[LayerPolicy] = None,
                             threshold: Optional[float] = None) -> LowRank:
        """x [B, S, H] → LowRank with dense outlier channel track.

        Each prompt decomposes independently (paper §3.1); outlier channel
        count is the static ``round(outlier_frac · H)`` with the layer's
        calibrated threshold (paper §4).
        """
        if lp is None:
            lp = self.layer_policy(layer_idx)
        if threshold is None:
            threshold = self.threshold(layer_idx)
        h_dim = x.shape[-1]
        num_c = max(1, round(lp.outlier_frac * h_dim)) \
            if lp.outlier_frac > 0 else 0
        x32 = x.astype(jnp.float32)
        if num_c:
            base, vals, idx = ol.extract(
                x32, jnp.asarray(threshold, jnp.float32), num_c)
        else:
            base = x32
        lr = self.decompose(base, lp.rank, iters=lp.effective_iters)
        lr = lr.astype(x.dtype)
        if num_c:
            lr = ol.attach_dense_outliers(lr, vals.astype(x.dtype), idx)
        return lr

    # -- KV-cache decomposition (serving) ---------------------------------
    def decompose_kv(self, x: Array, rank: int,
                     iters: Optional[int] = None,
                     exact: bool = False) -> Tuple[Array, Array]:
        """x [B, T, kvw] → (U·Σ [B, T, r], Vᵀ [B, r, kvw]).

        Lanczos through the engine backend for r ≪ min(T, kvw); ``exact``
        switches to direct SVD — used when r approaches full rank, where
        floating-point Lanczos loses trailing directions (§2.3).  The
        requested rank caps at min(T, kvw) — a factorization cannot carry
        more directions than the matrix has."""
        rank = min(rank, *x.shape[-2:])
        if exact:
            lr = from_dense_svd(x.astype(jnp.float32), rank)
        else:
            iters = iters or min(rank + self.config.kv_iters_extra,
                                 min(x.shape[-2:]))
            lr = self.decompose(x.astype(jnp.float32), rank, iters=iters)
        return lr.scaled_u().astype(x.dtype), lr.vt.astype(x.dtype)

    # -- stage 3: preserved-form consumption (paper §3.2) -----------------
    def project(self, lr: LowRank, wp, wfac: Optional[LowRank] = None
                ) -> LowRank:
        """Preserved matmul through a layer's weight dict ``{"w": …[, "b"]}``;
        uses the Eq. 7 input+weight chain when an offline weight factor is
        supplied."""
        if wfac is not None:
            y = lowrank_x_lowrank_weight(lr, wfac)
            if "b" in wp:
                y = add_bias_rank(y, wp["b"])   # exact rank-1 bias fold
            return y
        return lowrank_matmul(lr, wp["w"], bias=wp.get("b"))

    def qk_scores(self, q: LowRank, k: LowRank, num_heads: int, scale: float,
                  num_kv_heads: Optional[int] = None) -> Array:
        return preserved_qk_scores(q, k, num_heads, scale, num_kv_heads)

    def pv(self, p: Array, v: LowRank, num_heads: int,
           num_kv_heads: Optional[int] = None) -> Array:
        return preserved_pv(p, v, num_heads, num_kv_heads)

    def decompose_weight(self, w: Array, rank: int) -> LowRank:
        """Offline weight factorization (Table 3 mode) — exact SVD."""
        return decompose_weight(w, rank)

    def __repr__(self) -> str:
        exp = "auto" if self._auto_expansion else self.config.expansion
        return (f"DecomposeEngine(backend={self.backend.name!r}, "
                f"expansion={exp}, "
                f"attn_mode={self.config.attn_mode!r}, "
                f"kv_rank={self.config.kv_rank})")


def make_engine(policy=None, backend: str = "reference", **kw
                ) -> DecomposeEngine:
    """Convenience constructor: ``make_engine(policy, backend="pallas")``."""
    return DecomposeEngine(EngineConfig(policy=policy, backend=backend, **kw))
