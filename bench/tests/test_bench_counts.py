"""Operation and byte counts against numbers worked by hand: the dense
block's counted work, and the chips' peaks."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import counts, spec  # noqa: E402

DENSE = spec.block_module("dense")
GRANITE = spec.config_file("granite-3-2b")["model"]
DEEPSEEK_15L = {"num_layers": 15, "d_model": 4096, "num_heads": 32,
                "num_kv_heads": 32, "d_ff": 11008, "vocab": 102400,
                "gated_mlp": True}


def test_granite_forward_flops_by_hand():
    # per token and layer: q 2048x2048, k and v 2048x512 each, o 2048x2048,
    # three 2048x8192 MLP matrices: 2*(4194304 + 2097152 + 4194304 +
    # 50331648) = 121634816 FLOPs; 40 layers
    s = 1000
    per_token = 121_634_816 * 40
    attn = 40 * 4 * 32 * 64 * s * (s + 1) / 2
    head = 2 * 2048 * 49155
    assert DENSE.forward_flops(GRANITE, s) == per_token * s + attn + head


def test_deepseek_forward_flops_by_hand():
    # q, k, v, o all 4096x4096 (MHA) + three 4096x11008:
    # 2*(4*16777216 + 3*45088768) = 404750336 per token and layer
    s = 512
    want = 15 * (404_750_336 * s + 4 * 32 * 128 * s * (s + 1) / 2) \
        + 2 * 4096 * 102400
    assert DENSE.forward_flops(DEEPSEEK_15L, s) == want


def test_reorth_needed_by_hand():
    # granite, 1000-token prompt, rank 64 + 8: k = 72 steps, H = 512;
    # per matrix: 2*72 matvecs reading 1000*512*2 B, basis (1000+512)*4 B
    # times sum(j) = 2556 columns; K and V in 40 layers
    fl, by = DENSE.reorth_needed(GRANITE, 1000, 64, 8)
    per_by = 2 * 72 * 1000 * 512 * 2 + (1000 + 512) * 4 * 2556
    per_fl = 2 * 72 * 2 * 1000 * 512 + 8 * (1000 + 512) * 2556
    assert by == 80 * per_by and fl == 80 * per_fl
    fl, by = DENSE.reorth_needed(DEEPSEEK_15L, 1000, 64, 8)
    assert by == 30 * (2 * 72 * 1000 * 4096 * 2 + (1000 + 4096) * 4 * 2556)
    # memory bound on a v5e: bytes/819e9 > flops/197e12
    t, bound = counts.roofline_seconds(fl, by, "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(by / 819e9)


def test_peaks_are_keyed_by_device_kind():
    assert counts.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
