"""mellum2-12b [moe] — JetBrains Mellum2-12B-A2.5B: 28 layers, GQA 32/4
(head 128), three sliding-window layers (1024 rows, plain RoPE θ 5e5) then
one full-attention layer (YaRN θ 5e5, factor 16 over 8192 positions),
repeated 7 times; every layer a sparse MLP of 64 SwiGLU experts of width
896, top-8 with gates renormalized over the 8, no shared expert; untied
head, RMSNorm ε 1e-6.

The router keeps its published 64 experts; the preset holds one chip's
share of a four-chip expert-parallel deployment, experts 0-15 of every
layer (``replace(num_experts=64)`` holds the whole layer), so that it is
served on one chip.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]"""
from .base import ArchConfig, register

CONFIG = register(ArchConfig(
    name="mellum2-12b", family="moe",
    num_layers=28, d_model=2304, num_heads=32, num_kv_heads=4, head_dim=128,
    d_ff=7168, vocab=98304,
    num_experts=16, router_experts=64, expert_first=0, top_k=8,
    moe_d_ff=896, activation="silu", gated_mlp=True,
    rope_theta=500_000.0, norm_eps=1e-6,
    sliding_window=1024, full_attn_every=4,
    yarn_factor=16.0, yarn_original_max_pos=8192, yarn_beta_fast=32.0,
    yarn_beta_slow=1.0, yarn_attention_factor=1.2772588722239782,
    decompose_note=("full-attention layers only: their K/V is factorized "
                    "by Lanczos; window layers keep a ring of their last "
                    "sliding_window rows"),
))
