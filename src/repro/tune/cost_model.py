"""Analytic roofline cost model of the compute-expansion U-curve (Fig. 12).

Predicts the latency of one kernel launch as a function of the candidate
operating point, per (shape, dtype, device).  The model is the paper's own
explanation of Fig. 12 translated to a roofline (§5.3 + §6.4), reading the
chip peaks from the ``launch.roofline`` table:

* **memory side** (left of f*): the iterative chain is memory-bound and
  expansion unlocks bandwidth — f partial blocks stream concurrently, so
  utilized bandwidth is ``min(f, f_sat)/f_sat`` of aggregate.  This term is
  NON-INCREASING in f.
* **compute side** (right of f*): the element-wise/combine work is
  replicated per block (``dup·(f−1)``), the grid pays a fixed per-step cost
  (``steps·f·step_overhead`` — the dominant term in Pallas interpret mode),
  and padding the reduced axis to a multiple of f wastes arithmetic
  (``pad_waste``).  Every term is NON-DECREASING in f along a divisibility
  chain (the power-of-two grid in ``space.EXPANSION_GRID``).

``predict`` returns ``max(memory, compute)`` — the max of a non-increasing
and a non-decreasing function, hence provably UNIMODAL along the grid
(non-increasing up to its argmin, non-decreasing after).  The hypothesis
property in tests/test_properties.py pins exactly this.

The model is a PRUNER, not an oracle: the tuner ranks candidates with it
and measures only the survivors (``measure.py``), so constant errors
cancel and only the curve shape matters.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Mapping, Sequence, Tuple

from ..launch.roofline import V5E_KIND, chip_peaks

#: dtype-name → bytes (accepts jnp dtype names and numpy str())
DTYPE_BYTES = {"float64": 8, "float32": 4, "float16": 2, "bfloat16": 2,
               "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
               "int32": 4, "int8": 1}


@dataclasses.dataclass(frozen=True)
class DeviceModel:
    """Roofline denominators of one execution substrate."""
    name: str
    peak_flops: float            # FLOP/s
    hbm_bw: float                # bytes/s aggregate
    f_sat: int                   # blocks in flight at bandwidth saturation
    step_overhead_s: float       # fixed cost per grid step


def tpu_model(device_kind: str) -> DeviceModel:
    """Roofline model of a TPU kind from the peak table (KeyError for a
    kind the table does not list)."""
    pk = chip_peaks(device_kind)
    return DeviceModel(f"tpu:{device_kind}", pk.bf16_flops, pk.hbm_bw,
                       f_sat=8, step_overhead_s=1e-6)


#: TPU v5e — the deployment target.
V5E = tpu_model(V5E_KIND)

#: Pallas interpret mode on a CPU container: every grid step is executed by
#: the interpreter, so the per-step overhead dwarfs arithmetic and the model
#: correctly prefers small f.
CPU_INTERPRET = DeviceModel("cpu-interpret", 5e10, 2e10, f_sat=4,
                            step_overhead_s=2e-4)


def detect_device() -> DeviceModel:
    """Pick the device model for THIS process: a TPU's roofline from the
    peak table by its ``device_kind`` (an unlisted kind raises), anything
    else → interpret-mode CPU."""
    import jax
    if jax.default_backend() != "tpu":
        return CPU_INTERPRET
    return tpu_model(jax.devices()[0].device_kind)


def device_kind() -> str:
    """Stable cache-key string for the local accelerator."""
    import jax
    d = jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'device_kind', 'unknown')}"


def dtype_bytes(dtype: Any) -> int:
    return DTYPE_BYTES.get(str(dtype), 4)


def _padded(n: int, mult: int) -> int:
    return n + ((-n) % mult)


# ---------------------------------------------------------------------------
# Per-kernel term extraction
#
# Each function maps (shape, dtype_bytes, candidate) to the five roofline
# ingredients: (bytes_streamed, flops_base, dup_flops_per_extra_block,
# grid_steps_per_unit_f, pad_waste(f)).
# ---------------------------------------------------------------------------

Terms = Tuple[float, float, float, float, float]


def _terms_lanczos_reorth(shape: Sequence[int], dtb: int,
                          cand: Mapping[str, Any]) -> Terms:
    """One fused CGS2 re-orth launch, grid = (B, 3, f) over [B, S, H]
    against a k-column Q buffer (shape may carry k as a 4th dim)."""
    if len(shape) == 4:
        b, s, h, k = shape
    else:
        (b, s, h), k = tuple(shape), 16
    f = cand["expansion"]
    s_pad, h_pad = _padded(s, f), _padded(h, f)
    bytes_streamed = b * (3 * s * h * dtb + 2 * (s + h) * k * 4)
    flops_base = b * (2 * s * h + 8 * (s + h) * k)
    dup = b * 4 * (s + h) * k            # replicated correction/combine
    steps = 3 * b                        # grid steps per unit of f
    waste = (s_pad * h_pad) / float(s * h)
    return bytes_streamed, flops_base, dup, steps, waste


def _terms_matvec_expand(shape: Sequence[int], dtb: int,
                         cand: Mapping[str, Any]) -> Terms:
    """y = A·v with the H reduction expanded f ways; grid=(S/rb, f)."""
    if len(shape) == 3:
        b, s, h = shape
    else:
        (s, h), b = tuple(shape), 1
    f = cand["expansion"]
    rb = min(cand.get("row_block", 512), s)
    bytes_streamed = b * s * h * dtb
    flops_base = 2 * b * s * h
    dup = 2 * b * s                      # per-block partial re-accumulate
    steps = b * max(1, -(-s // rb))
    waste = _padded(h, f) / float(h)
    return bytes_streamed, flops_base, dup, steps, waste


def _terms_lowrank_matmul(shape: Sequence[int], dtb: int,
                          cand: Mapping[str, Any]) -> Terms:
    """Vᵀ[k,H] @ W[H,N], H reduction expanded f ways; grid=(N/nb, f)."""
    k, h, n = shape
    f = cand["expansion"]
    nb = min(cand.get("n_block", 512), n)
    k_pad = max(8, -(-k // 8) * 8)
    bytes_streamed = h * n * dtb + k_pad * h * dtb
    flops_base = 2 * k_pad * h * n
    dup = 2 * k_pad * n                  # per-block output re-accumulate
    steps = max(1, -(-n // nb))
    waste = _padded(h, f) / float(h)
    return bytes_streamed, flops_base, dup, steps, waste


def _terms_dkv_attention(shape: Sequence[int], dtb: int,
                         cand: Mapping[str, Any]) -> Terms:
    """Rank-space flash stats over U_k/U_v [T, r], grid=(f,) time blocks."""
    g, t, r = shape
    f = cand["expansion"]
    bytes_streamed = 2 * t * r * dtb
    flops_base = 4 * g * t * r
    dup = 4 * g * r                      # accumulator rescale per block
    steps = 1
    waste = _padded(t, f) / float(t)
    return bytes_streamed, flops_base, dup, steps, waste


KERNEL_TERMS: Dict[str, Callable[[Sequence[int], int, Mapping[str, Any]],
                                 Terms]] = {
    "lanczos_reorth": _terms_lanczos_reorth,
    "matvec_expand": _terms_matvec_expand,
    "lowrank_matmul": _terms_lowrank_matmul,
    "dkv_attention": _terms_dkv_attention,
}

#: Host→device dispatch + sync cost of ONE decode launch (python driver,
#: jit call, logits device→host).  Dominant on small models; the fused
#: loop divides it by the block length.
HOST_DISPATCH_S = 2e-4


def _predict_decode_block(shape: Sequence[int], dtb: int,
                          cand: Mapping[str, Any],
                          dev: DeviceModel) -> float:
    """Per-TOKEN seconds of the fused serving decode loop at block length
    k, for shape (slots b, decode horizon t, kv row width w).

    ``t_step`` is the roofline time of one decode step (stream the [b,t,w]
    K/V working set once, 4·b·t·w flops of attention contraction); on top
    the host dispatch amortizes as ``HOST_DISPATCH_S / min(k, t)`` (a
    block can't outrun the fold/budget horizon ``t``) and a small linear
    penalty models the wasted tail of over-long blocks (early exits and
    horizon caps throw away trace length).  Non-increasing amortization +
    non-decreasing penalty ⇒ unimodal in k along the power-of-two grid,
    matching the expansion model's pruning contract."""
    b, t, w = shape
    k = int(cand["block"])
    if k < 1:
        raise ValueError(f"block must be >= 1, got {k}")
    t_step = max(2 * b * t * w * dtb / dev.hbm_bw,
                 4.0 * b * t * w / dev.peak_flops)
    k_eff = min(k, max(1, t))
    overshoot = (k - k_eff) / float(k)   # trace beyond any usable horizon
    return t_step + HOST_DISPATCH_S / k_eff \
        + t_step * overshoot + 1e-7 * k


def predict(kernel: str, shape: Sequence[int], dtype: Any,
            cand: Mapping[str, Any],
            device: DeviceModel = None) -> float:
    """Predicted seconds for one launch of ``kernel`` at operating point
    ``cand`` — max(memory term, compute term), unimodal in the expansion
    factor along a power-of-two grid.  (For the ``decode_block`` pseudo
    kernel the objective is per-token seconds of the serving loop.)"""
    dev = device or detect_device()
    if kernel == "decode_block":
        return _predict_decode_block(shape, dtype_bytes(dtype), cand, dev)
    try:
        terms = KERNEL_TERMS[kernel]
    except KeyError:
        raise KeyError(f"no cost model for kernel {kernel!r}; "
                       f"known: {sorted(KERNEL_TERMS)}") from None
    f = int(cand["expansion"])
    if f < 1:
        raise ValueError(f"expansion must be >= 1, got {f}")
    bytes_streamed, flops_base, dup, steps, waste = \
        terms(shape, dtype_bytes(dtype), cand)
    bw = dev.hbm_bw * min(f, dev.f_sat) / dev.f_sat
    t_mem = bytes_streamed / bw
    t_comp = (flops_base * waste + dup * (f - 1)) / dev.peak_flops \
        + steps * f * dev.step_overhead_s
    return max(t_mem, t_comp)


def predict_curve(kernel: str, shape: Sequence[int], dtype: Any,
                  candidates: Sequence[Mapping[str, Any]],
                  device: DeviceModel = None
                  ) -> Tuple[Tuple[Dict[str, Any], float], ...]:
    """(candidate, predicted_s) per candidate, in candidate order."""
    dev = device or detect_device()
    return tuple((dict(c), predict(kernel, shape, dtype, c, dev))
                 for c in candidates)
