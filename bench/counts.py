"""Operations and bytes the served work needs, and the chips' peaks.

Everything here is computed from the configuration's sizes and the real
(unpadded) request lengths, never from what a kernel streams today: bucket
padding, batch padding and re-reads lower a roofline share instead of
raising the count.
"""
from __future__ import annotations

from typing import Dict, Tuple

#: ``jax.Device.device_kind`` → published per-chip peaks.  A kind that is
#: not here is an error, not a default.
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def _sizes(model: dict) -> Tuple[int, int, int, int, int, int, int]:
    d, nh, kvh = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // nh
    return (model["num_layers"], d, nh, kvh, hd, model["d_ff"],
            model["vocab"])


def forward_flops(model: dict, prompt_len: int) -> float:
    """FLOPs of one prompt's prefill forward: every projection and MLP
    matmul for each prompt token, causal attention (scores and values over
    the ``s·(s+1)/2`` visible pairs), and the head for the one position
    whose logits are sampled."""
    nl, d, nh, kvh, hd, ff, vocab = _sizes(model)
    s = int(prompt_len)
    mlp_mats = 3 if model.get("gated_mlp", True) else 2
    per_token = 2 * (d * nh * hd + 2 * d * kvh * hd + nh * hd * d
                     + mlp_mats * d * ff)
    attn = 2 * 2 * nh * hd * s * (s + 1) / 2
    return nl * (per_token * s + attn) + 2 * d * vocab


def reorth_needed(model: dict, prompt_len: int, rank: int,
                  iters_extra: int, a_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) the Lanczos re-orthogonalization of one prompt's K
    and V needs, in every layer.

    Each of ``k = rank + iters_extra`` steps makes two matvecs (Aᵀu, then
    Av), each reading the ``s × kvw`` activation once at ``a_bytes`` per
    element (the dtype prefill wrote), and projects against the Lanczos
    basis built so far (``j`` columns of float32 at step ``j``) with
    classical Gram–Schmidt applied twice."""
    nl, _, _, kvh, hd, _, _ = _sizes(model)
    s, h = int(prompt_len), kvh * hd
    k = int(rank) + int(iters_extra)
    basis_cols = k * (k - 1) / 2                 # sum of j over the steps
    by = 2 * k * s * h * a_bytes + (s + h) * 4 * basis_cols
    fl = 2 * k * 2 * s * h + 2 * 2 * 2 * (s + h) * basis_cols
    return 2 * nl * fl, 2 * nl * by


def roofline_seconds(flops: float, nbytes: float, device_kind: str
                     ) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    pk = peaks(device_kind)
    tf, tb = flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")
