"""Model/config dataclasses shared by all architectures."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    vocab_size: int
    # attention
    n_heads: int = 0
    n_kv_heads: int = 0
    d_head: int = 128
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    # mlp
    d_ff: int = 0
    mlp_type: str = "swiglu"  # swiglu | geglu | gelu
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    # moe
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    # ssm (mamba2 / hybrid)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    conv_kernel: int = 4
    ssm_chunk: int = 128
    # encdec
    n_encoder_layers: int = 0
    encoder_seq: int = 1500
    # vlm
    n_vision_tokens: int = 256
    # execution
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    q_chunk: int = 512
    kv_chunk: int = 512
    # Decode cache via fori_loop carry + dynamic-update-slice instead of
    # scan xs→ys: lets XLA forward the cache buffer in place rather than
    # double-buffering old/new caches.
    decode_inplace_cache: bool = False
    # Megatron-style sequence parallelism for attention:
    # residual stream + q seq-sharded over 'model', K/V all-gathered, FFN
    # all-gather/reduce-scatter inserted by GSPMD. Requires ambient mesh.
    seq_parallel_attn: bool = False
    # when True the MoE dispatch path calls the Pallas grouped_matmul
    # kernel (interpret mode on CPU); False keeps the einsum path. Math is
    # identical (tested).
    moe_pallas_dispatch: bool = False

    # ------------------------------------------------------------ derived
    @property
    def attn_dim(self) -> int:
        return self.n_heads * self.d_head

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.d_head

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def scaled(self, **overrides) -> "ModelConfig":
        """Reduced copy for smoke tests."""
        return dataclasses.replace(self, **overrides)
