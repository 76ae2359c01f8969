"""Architecture catalog: the 10 assigned configs + reduced smoke variants."""
from __future__ import annotations

from .base import ModelConfig  # noqa: F401
from .dbrx_132b import CONFIG as _dbrx
from .deepseek_coder_33b import CONFIG as _deepseek
from .hymba_1p5b import CONFIG as _hymba
from .mamba2_2p7b import CONFIG as _mamba2
from .paligemma_3b import CONFIG as _paligemma
from .qwen2_7b import CONFIG as _qwen2
from .qwen3_moe_235b import CONFIG as _qwen3moe
from .starcoder2_7b import CONFIG as _starcoder2
from .whisper_large_v3 import CONFIG as _whisper
from .yi_34b import CONFIG as _yi

ARCHS = {
    c.name: c
    for c in [_starcoder2, _deepseek, _yi, _qwen2, _paligemma, _mamba2,
              _qwen3moe, _dbrx, _hymba, _whisper]
}


def smoke_config(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    over = dict(
        n_layers=2, d_model=64, vocab_size=128,
        param_dtype="float32", compute_dtype="float32",
        q_chunk=32, kv_chunk=32, remat=False,
    )
    if cfg.n_heads:
        over.update(n_heads=4, n_kv_heads=max(1, min(2, cfg.n_kv_heads)),
                    d_head=16)
    if cfg.d_ff:
        over.update(d_ff=128)
    if cfg.family == "moe":
        over.update(n_experts=4, experts_per_token=2, moe_d_ff=64)
    if cfg.family in ("ssm", "hybrid"):
        over.update(ssm_state=8, ssm_head_dim=16, ssm_chunk=16)
    if cfg.family == "encdec":
        over.update(n_encoder_layers=2, encoder_seq=24)
    if cfg.family == "vlm":
        over.update(n_vision_tokens=8)
    return cfg.scaled(**over)
