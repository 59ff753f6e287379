"""Config schema for architectures and run shapes.

One :class:`ModelConfig` per assigned architecture lives in
``repro/configs/<id>.py``; ``smoke()`` derives the reduced-config variant
used by per-arch CPU smoke tests.  Run shapes (the assigned seq/batch cells)
are :class:`RunShape` instances in ``SHAPES``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

__all__ = ["ModelConfig", "RunShape", "SHAPES"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None          # default d_model // n_heads
    # positional encoding
    rope: str = "rope"                      # rope | rope_half | rope2d | mrope | none
    rope_theta: float = 10000.0
    mrope_sections: Tuple[int, int, int] = (16, 24, 24)  # t/h/w split of half-dims
    # MoE
    n_experts: int = 0
    experts_per_tok: int = 0
    moe_capacity_factor: float = 1.25
    # SSM (mamba2) / linear attention (rwkv6)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_ngroups: int = 1                    # mamba2: B and C groups over the heads
    ssm_conv_bias: bool = False
    # hybrid (zamba2): shared attention blocks, used in turn (use u takes
    # block u mod num_mem_blocks) before the Mamba2 layers hybrid_layer_ids
    # names; each use has its own rank-adapter_rank LoRA on the MLP's
    # gate_up and its own d_model x d_model linear into the Mamba input
    hybrid_layer_ids: Tuple[int, ...] = ()
    num_mem_blocks: int = 0
    adapter_rank: int = 0
    # encoder-decoder
    enc_layers: int = 0                     # >0 => enc-dec; n_layers = decoder
    cross_attention: bool = False
    # misc
    activation: str = "swiglu"              # swiglu | gelu
    norm: str = "rmsnorm"                   # rmsnorm | layernorm
    norm_eps: float = 1e-6                  # read by the hybrid family's norms
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    kv_cache_dtype: str = "bfloat16"   # "float8_e4m3fn" = quantized KV pages
    # frontends ([audio]/[vlm]): backbone consumes precomputed embeddings
    frontend: Optional[str] = None          # None | audio | vision
    notes: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def hybrid_uses(self) -> Tuple[int, ...]:
        """The Mamba2 layers a shared block runs before, within the depth."""
        return tuple(i for i in self.hybrid_layer_ids if i < self.n_layers)

    @property
    def kv_layers(self) -> int:
        """Layers that keep attention KV: a hybrid's shared-block uses,
        every layer of the other attention families."""
        return len(self.hybrid_uses) if self.family == "hybrid" else self.n_layers

    @property
    def is_encdec(self) -> bool:
        return self.enc_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch run the long_500k cell? (SSM/hybrid/linear-attn)"""
        return self.family in ("ssm", "hybrid")

    def n_params(self) -> int:
        """Analytic parameter count (embeddings included once)."""
        d, f, L = self.d_model, self.d_ff, self.n_layers
        hd, H, KV = self.hd, self.n_heads, self.n_kv_heads
        attn = d * H * hd + 2 * d * KV * hd + H * hd * d
        if self.activation == "swiglu":
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f
        if self.n_experts:
            mlp = self.n_experts * (3 * d * f) + d * self.n_experts
        if self.family == "ssm":  # rwkv6-style block
            att_d = d
            attn = 4 * d * att_d + att_d * d + 6 * d * 32 * 2  # rkvg + out + lora-ish mixers
        per_layer = attn + mlp + 2 * d
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        total = L * per_layer + emb
        if self.is_encdec:
            enc_per = attn + mlp + 2 * d
            total += self.enc_layers * enc_per + L * attn  # cross-attn
        if self.family == "hybrid":
            d_in = self.ssm_expand * d
            nh = d_in // self.ssm_head_dim
            conv_dim = d_in + 2 * self.ssm_ngroups * self.ssm_state
            mamba = (d * (d_in + conv_dim + nh) + self.ssm_conv * conv_dim
                     + conv_dim * self.ssm_conv_bias + 3 * nh + d_in + d_in * d + d)
            # a shared block reads the 2*d concat of residual and embedding
            shared = (2 * d + 2 * d * H * hd + 2 * 2 * d * KV * hd + H * hd * d + d
                      + 3 * d * f)
            per_use = self.adapter_rank * (d + 2 * f) + d * d
            total = (L * mamba + self.num_mem_blocks * shared
                     + len(self.hybrid_uses) * per_use + emb + d)
        return int(total)

    def n_active_params(self) -> int:
        """Active params per token (MoE: only routed experts)."""
        if not self.n_experts:
            return self.n_params()
        d, f, L = self.d_model, self.d_ff, self.n_layers
        hd, H, KV = self.hd, self.n_heads, self.n_kv_heads
        attn = d * H * hd + 2 * d * KV * hd + H * hd * d
        mlp_active = self.experts_per_tok * (3 * d * f) + d * self.n_experts
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return int(L * (attn + mlp_active + 2 * d) + emb)

    def smoke(self) -> "ModelConfig":
        """Reduced same-family config for CPU smoke tests."""
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=5 if self.hybrid_layer_ids else max(2, min(3, self.n_layers)),
            d_model=128,
            n_heads=4,
            n_kv_heads=max(1, min(self.n_kv_heads, 2)) if self.n_kv_heads < self.n_heads else 4,
            head_dim=32,
            d_ff=64 if self.n_experts else 256,
            vocab_size=512,
            n_experts=min(self.n_experts, 8) if self.n_experts else 0,
            experts_per_tok=min(self.experts_per_tok, 2) if self.n_experts else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=16 if self.ssm_state else 64,
            enc_layers=2 if self.enc_layers else 0,
            hybrid_layer_ids=(1, 2, 4) if self.hybrid_layer_ids else (),
            adapter_rank=min(self.adapter_rank, 8),
            mrope_sections=(4, 6, 6),
            dtype="float32",
            kv_cache_dtype="float32",
        )


@dataclasses.dataclass(frozen=True)
class RunShape:
    name: str
    seq_len: int
    global_batch: int
    mode: str            # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.mode == "decode"


SHAPES = {
    "train_4k": RunShape("train_4k", 4096, 256, "train"),
    "prefill_32k": RunShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": RunShape("decode_32k", 32768, 128, "decode"),
    "long_500k": RunShape("long_500k", 524288, 1, "decode"),
}
