"""Architecture + shape registries (deliverable f).

Every assigned architecture is an ``ArchConfig``; every workload shape is a
``ShapeSpec``.  The dry-run iterates the cross product; smoke tests use
``reduced()`` variants of the same configs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One architecture from the assigned pool (plus the paper's Llama-2-7b)."""
    name: str
    family: str                      # dense | moe | hybrid | vlm | ssm | audio
    num_layers: int
    d_model: int
    num_heads: int                   # 0 for attention-free (mamba2)
    num_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None   # default d_model // num_heads
    activation: str = "silu"         # silu | geglu | gelu
    gated_mlp: bool = True
    use_bias: bool = False
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    # --- attention kinds (mellum2) ---
    sliding_window: int = 0          # window layers attend to this many rows
    full_attn_every: int = 0         # with a window: layer i is full iff
                                     # (i + 1) % full_attn_every == 0
    # YaRN scaled RoPE of the full-attention layers (0 = plain RoPE);
    # window layers keep plain RoPE at rope_theta
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    # --- MoE ---
    num_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                # per-expert FFN dim (d_ff used for dense)
    n_shared_experts: int = 0
    first_k_dense: int = 0           # leading dense layers (kimi-style)
    capacity_factor: float = 1.25
    # A chip's share of the experts: the router keeps its published width
    # ``router_experts`` and this chip holds experts ``expert_first`` …
    # ``expert_first + num_experts − 1``.  A config that sets it runs the
    # drop-free held-expert layer (``moe.held_experts_ffn``); 0 keeps the
    # capacity-factor ``moe_ffn`` with the router as wide as num_experts.
    router_experts: int = 0
    expert_first: int = 0
    expert_sharding: str = "1d"      # "1d" = EP only; "2d" = EP x data (1T)
    # --- SSM (mamba2) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    # --- hybrid (zamba2) ---
    attn_period: int = 0             # one shared attention block every N layers
    # --- VLM ---
    cross_attn_period: int = 0       # cross-attn layer every N layers
    num_image_tokens: int = 0
    # --- enc-dec (audio) ---
    enc_layers: int = 0              # decoder layers = num_layers - enc_layers
    num_audio_frames: int = 0        # encoder memory length for decode shapes
    # --- numerics / training ---
    dtype: str = "bfloat16"
    norm_eps: float = 1e-5
    optimizer: str = "adamw"         # adafactor for the 1T MoE
    remat: bool = True               # activation checkpointing per block
    remat_policy: str = "full"       # full | dots (save matmul outputs)
    seq_parallel: bool = False       # Megatron-SP: residual stream sharded
                                     # [.., S/model, H]; AG before attn/MLP,
                                     # RS after (beyond-paper perf knob)
    # --- paper technique applicability note (DESIGN.md §Arch-applicability) ---
    decompose_note: str = "full"

    # ------------------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        """Embedding/head table rows padded to a 128 multiple (Megatron-style)
        so the vocab dim shards on any mesh axis and aligns to the MXU; the
        logits tail is masked in ``logits_head``.  Logical ``vocab`` is
        unchanged (granite 49155→49280, seamless 256206→256256,
        mamba2 50280→50304)."""
        return (self.vocab + 127) // 128 * 128

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """``"window"`` or ``"full"`` for each decoder layer."""
        if not self.sliding_window:
            return ("full",) * self.num_layers
        n = self.full_attn_every
        return tuple("full" if n and (i + 1) % n == 0 else "window"
                     for i in range(self.num_layers))

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def dec_layers(self) -> int:
        return self.num_layers - self.enc_layers

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch supports O(1)-state long-context decode."""
        return self.family in ("ssm", "hybrid")

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ArchConfig":
        """Tiny same-family variant for CPU smoke tests."""
        kw = dict(
            name=self.name + "-smoke",
            num_layers=min(self.num_layers, 4 if self.attn_period or
                           self.cross_attn_period else 2),
            d_model=128,
            num_heads=min(self.num_heads, 4) if self.num_heads else 0,
            num_kv_heads=min(self.num_kv_heads, 2) if self.num_kv_heads else 0,
            head_dim=32 if self.num_heads else None,
            d_ff=256,
            vocab=512,
            remat=False,
        )
        if self.num_experts:
            kw.update(num_experts=8, top_k=2, moe_d_ff=64,
                      n_shared_experts=min(self.n_shared_experts, 1),
                      first_k_dense=min(self.first_k_dense, 1))
        if self.ssm_state:
            kw.update(ssm_state=16, ssm_head_dim=32)
        if self.attn_period:
            kw.update(attn_period=2)
        if self.cross_attn_period:
            kw.update(cross_attn_period=2, num_image_tokens=16)
        if self.enc_layers:
            kw.update(num_layers=4, enc_layers=2, num_audio_frames=32)
        if self.router_experts:
            kw.update(num_experts=4, router_experts=8, top_k=2)
        if self.sliding_window:
            # one whole period of the layer pattern, a window shorter than
            # the smoke prompts
            kw.update(num_layers=self.full_attn_every or 2, sliding_window=8)
        return self.replace(**kw)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One workload shape (LM-family shared set)."""
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    if not _REGISTRY:
        _load_all()
    return _REGISTRY[name]


def all_archs() -> Dict[str, ArchConfig]:
    if not _REGISTRY:
        _load_all()
    return dict(_REGISTRY)


def _load_all() -> None:
    # Import every per-arch module once; each calls register().
    from . import (deepseek_7b, gemma_2b, granite_3_2b,  # noqa: F401
                   kimi_k2, llama2_7b, llama32_vision_11b, mamba2_780m,
                   mellum2_12b, olmoe_1b_7b, seamless_m4t_medium,
                   starcoder2_7b, zamba2_1_2b)


def cells(arch: ArchConfig) -> Tuple[str, ...]:
    """Shape names that apply to this arch (long_500k only for sub-quadratic;
    skip recorded in DESIGN.md §5 / EXPERIMENTS.md §Dry-run)."""
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if arch.sub_quadratic:
        names.append("long_500k")
    return tuple(names)
