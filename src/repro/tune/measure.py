"""Empirical measurement harness: jit warmup + median-of-k wall clock.

The cost model (``cost_model.py``) ranks candidates; this module times the
survivors on the REAL kernels with deterministic synthetic inputs.  Every
benchmark closure goes through the same public entry points the engine
uses (``kernels.ops`` wrappers, which pad via the cached pad plans), so
the measured number includes the padding and dispatch cost the production
path pays.

Kernel imports are lazy (function-local): kernel modules import
``tune.space`` at definition time to register their spaces, so this module
must not import them back at module level.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp


def timeit(fn: Callable[[], Any], *, warmup: int = 2, reps: int = 5
           ) -> float:
    """Median wall-clock seconds per call (blocks on jax outputs).

    True median: the two middle samples are averaged for even ``reps``
    (``ts[k//2]`` alone would be the MAX at reps=2 — worst-case, not
    typical-case, and needlessly noisy as a ranking signal)."""
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    k = len(ts)
    return (ts[k // 2] + ts[(k - 1) // 2]) / 2.0


def _rand(key: int, shape: Sequence[int], dtype) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(key), tuple(shape),
                             jnp.float32).astype(dtype)


def _bench_lanczos_reorth(shape, dtype, cand) -> Callable[[], Any]:
    """One fused right re-orth step over [B, S, H] against a k-column
    buffer, through the candidate backend's own hooks — exactly what the
    engine executes with that backend (interpret mode included)."""
    from ..engine.backends import get_backend
    from ..kernels import ops
    if len(shape) == 4:
        b, s, h, k = shape
    else:
        (b, s, h), k = tuple(shape), 16
    f = int(cand["expansion"])
    backend = get_backend(cand["backend"])
    if backend.requires_padding:
        s, h = ops.padded_dims(s, h, f)
    a = _rand(0, (b, s, h), dtype)
    u = _rand(1, (b, s), jnp.float32)
    vbuf = jnp.zeros((b, h, k), jnp.float32)
    step = jax.jit(backend.make_hooks(f).right_step)
    return lambda: step(a, u, vbuf)


def _bench_matvec_expand(shape, dtype, cand) -> Callable[[], Any]:
    if len(shape) == 3:
        b, s, h = shape
        a = _rand(0, (b, s, h), dtype)
        v = _rand(1, (b, h), dtype)

        def run():
            from ..kernels import ops
            return ops.matvec_batched(a, v, expansion=int(cand["expansion"]),
                                      row_block=cand.get("row_block"))
        return run
    s, h = shape
    a = _rand(0, (s, h), dtype)
    v = _rand(1, (h,), dtype)

    def run():
        from ..kernels import ops
        return ops.matvec(a, v, expansion=int(cand["expansion"]),
                          row_block=cand.get("row_block"))
    return run


def _bench_lowrank_matmul(shape, dtype, cand) -> Callable[[], Any]:
    k, h, n = shape
    vt = _rand(0, (k, h), dtype)
    w = _rand(1, (h, n), dtype)

    def run():
        from ..kernels import ops
        return ops.lowrank_matmul(vt, w, expansion=int(cand["expansion"]),
                                  n_block=cand.get("n_block"))
    return run


def _bench_dkv_attention(shape, dtype, cand) -> Callable[[], Any]:
    g, t, r = shape
    inner = _rand(0, (g, r), jnp.float32)
    k_u = _rand(1, (t, r), dtype)
    v_u = _rand(2, (t, r), dtype)

    def run():
        from ..kernels import ops
        return ops.dkv_attention_stats(inner, k_u, v_u,
                                       expansion=int(cand["expansion"]))
    return run


def _bench_decode_block(shape, dtype, cand) -> Callable[[], Any]:
    """Serving decode-loop proxy, normalized PER TOKEN: every candidate
    decodes the same 32 tokens, block length k just repartitions them into
    ``ceil(32/k)`` jitted ``fori_loop`` launches (each launch blocks, like
    the engine's per-block host sync), so the measured per-call medians
    are comparable across k after the caller's own normalization — the
    tuner minimizes median seconds per call, hence we fold the
    launch-count difference into the closure by running ALL launches of
    one 32-token decode per call."""
    b, t, w = shape
    k = int(cand["block"])
    tokens = 32
    launches = max(1, -(-tokens // k))
    kv = _rand(0, (b, t, w), dtype)
    q0 = _rand(1, (b, w), jnp.float32)

    @jax.jit
    def block(q, kv):
        def body(_, q):
            s = jnp.einsum("bw,btw->bt", q, kv.astype(jnp.float32))
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("bt,btw->bw", p, kv.astype(jnp.float32))
        return jax.lax.fori_loop(0, k, body, q)

    def run():
        q = q0
        for _ in range(launches):
            q = jax.block_until_ready(block(q, kv))
        return q
    return run


_BENCH = {
    "lanczos_reorth": _bench_lanczos_reorth,
    "matvec_expand": _bench_matvec_expand,
    "lowrank_matmul": _bench_lowrank_matmul,
    "dkv_attention": _bench_dkv_attention,
    "decode_block": _bench_decode_block,
}


def measure_candidate(kernel: str, shape: Sequence[int], dtype: Any,
                      cand: Mapping[str, Any], *, warmup: int = 2,
                      reps: int = 5) -> float:
    """Median seconds per launch of ``kernel`` at operating point ``cand``
    on deterministic synthetic inputs of ``shape``/``dtype``."""
    try:
        builder = _BENCH[kernel]
    except KeyError:
        raise KeyError(f"no measurement harness for kernel {kernel!r}; "
                       f"known: {sorted(_BENCH)}") from None
    fn = builder(tuple(int(d) for d in shape), jnp.dtype(dtype), dict(cand))
    return timeit(fn, warmup=warmup, reps=reps)
