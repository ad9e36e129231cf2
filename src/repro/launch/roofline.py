"""Pure roofline helpers (no jax device-state side effects on import).

``launch.dryrun`` (which MUST set XLA_FLAGS before any jax import) re-uses
these; tests import from here so the pytest process keeps its single
device.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict

# ---------------------------------------------------------------------------
# Published chip peaks (roofline denominators), keyed by device_kind
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks of one accelerator kind."""
    bf16_flops: float            # FLOP/s
    hbm_bw: float                # bytes/s
    ici_link_bw: float           # bytes/s per interconnect link
    source: str


#: ``jax.Device.device_kind`` → peaks.  The one table every roofline in the
#: repo reads (cost model, dry-run, benchmarks); a kind missing here is an
#: error, never a default.
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, hbm_bw=819e9, ici_link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI "
               "(4 links x 50 GB/s)"),
}
#: device_kind JAX reports for a TPU v5e chip (the dry-run's target).
V5E_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; raises KeyError for a kind not in
    :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "u64": 8, "s64": 8,
                "u32": 4, "s32": 4, "u16": 2, "s16": 2, "u8": 1, "s8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# Ring-schedule per-device traffic multiplier relative to RESULT bytes
# (documented convention, EXPERIMENTS.md §Roofline): all-reduce moves ~2×
# payload per device; all-gather/reduce-scatter/all-to-all/permute ~1×.
_RING_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every shape literal in an HLO result type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Per-collective-type payload bytes + op counts from optimized HLO."""
    stats = {k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"%?\S+ = (\([^)]*\)|\S+) ([a-z\-]+)", line)
        if not m:
            continue
        op = m.group(2)
        # normalize fusion-start variants like "all-gather-start"
        base = op.replace("-start", "").replace("-done", "")
        if base in _COLLECTIVES and not op.endswith("-done"):
            stats[base]["bytes"] += _shape_bytes(m.group(1))
            stats[base]["count"] += 1
    return stats


def roofline_terms(flops_per_dev: float, hbm_bytes_per_dev: float,
                   coll: Dict[str, Any]) -> Dict[str, float]:
    """Three roofline terms in seconds (all PER-DEVICE quantities).

    cost_analysis of the SPMD-partitioned module is per-device, so we divide
    by single-chip v5e peaks (equivalent to global/chips — see
    EXPERIMENTS.md).
    """
    pk = chip_peaks(V5E_KIND)
    coll_bytes = sum(v["bytes"] * _RING_FACTOR[k] for k, v in coll.items())
    return {
        "compute_s": flops_per_dev / pk.bf16_flops,
        "memory_s": hbm_bytes_per_dev / pk.hbm_bw,
        "collective_s": coll_bytes / pk.ici_link_bw,
        "collective_bytes": coll_bytes,
    }


def probe_plan(cfg):
    """[(probe_cfg, n_units)] ×2 + n_units_full for linear extrapolation of
    while-body-undercounted costs (see dryrun.calibrate)."""
    if cfg.family == "vlm":
        per = cfg.cross_attn_period
        mk = lambda g: cfg.replace(num_layers=g * per)
        return [(mk(1), 1), (mk(2), 2)], cfg.num_layers // per
    if cfg.family == "hybrid":
        per = cfg.attn_period
        mk = lambda g: cfg.replace(num_layers=g * per)
        # tail mamba layers folded into the per-layer average (documented)
        return [(mk(1), per), (mk(2), 2 * per)], cfg.num_layers
    if cfg.family == "audio":
        mk = lambda p: cfg.replace(num_layers=2 * p, enc_layers=p,
                                   num_audio_frames=cfg.num_audio_frames)
        return [(mk(1), 1), (mk(2), 2)], cfg.enc_layers
    if cfg.family == "moe" and cfg.first_k_dense:
        mk = lambda m: cfg.replace(num_layers=cfg.first_k_dense + m)
        return [(mk(1), 1), (mk(2), 2)], cfg.num_layers - cfg.first_k_dense
    mk = lambda n: cfg.replace(num_layers=n)
    return [(mk(1), 1), (mk(2), 2)], cfg.num_layers
