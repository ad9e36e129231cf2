"""Shared benchmark helpers: timing, CSV rows, v5e roofline cost model,
and the one sanctioned artifact writer."""
from __future__ import annotations

import time
from typing import Any, Callable, List, Tuple

import jax

from repro.ioutil import atomic_write_json
from repro.launch.roofline import V5E_KIND, chip_peaks

# TPU v5e roofline denominators, read from the one peak table
_V5E = chip_peaks(V5E_KIND)
PEAK_FLOPS = _V5E.bf16_flops
HBM_BW = _V5E.hbm_bw

Row = Tuple[str, float, str]      # (name, us_per_call, derived-info)


def wall(fn: Callable, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall-clock seconds per call (blocks on jax outputs)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def v5e_time(flops: float, bytes_moved: float) -> float:
    """Roofline latency model on one v5e chip: max(compute, memory)."""
    return max(flops / PEAK_FLOPS, bytes_moved / HBM_BW)


def emit(rows: List[Row]) -> None:
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived}")


def write_json(path: str, obj: Any, **dump_kw: Any) -> None:
    """Write a benchmark report artifact.

    Every ``benchmarks/*.py`` report goes through here: atomic
    tmp+``os.replace`` via ``repro.ioutil`` (parent dirs created), so
    dcomlint rule D3 holds by construction — CI tailing an artifact mid
    re-write sees the previous complete report, never a truncated one.
    """
    atomic_write_json(path, obj, **dump_kw)
