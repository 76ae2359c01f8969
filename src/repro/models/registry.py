"""Model registry: family -> (init, loss, prefill, decode_step, init_cache).

All entries share the same functional API so the trainer and server are
family-agnostic:

    api = get_model(cfg)
    params = api.init(key)
    loss   = api.loss(params, batch)          # batch: dict of arrays
    logits, cache = api.prefill(params, batch, max_len)
    logits, cache = api.decode_step(params, cache, tokens)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

from . import encdec, hybrid, ssm_lm, transformer, vlm


@dataclasses.dataclass(frozen=True)
class ModelApi:
    cfg: object
    init: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    init_cache: Callable


def _lm_prefill(mod, cfg, params, batch, max_len, ctx=None):
    return mod.prefill(cfg, params, batch["tokens"], max_len, ctx)


def get_model(cfg) -> ModelApi:
    fam = cfg.family
    if fam in ("dense", "moe"):
        mod = transformer
        prefill = functools.partial(_lm_prefill, mod, cfg)
    elif fam == "ssm":
        mod = ssm_lm
        prefill = functools.partial(_lm_prefill, mod, cfg)
    elif fam == "hybrid":
        mod = hybrid
        prefill = functools.partial(_lm_prefill, mod, cfg)
    elif fam == "encdec":
        mod = encdec
        prefill = functools.partial(mod.prefill, cfg)
    elif fam == "vlm":
        mod = vlm
        prefill = functools.partial(mod.prefill, cfg)
    else:
        raise ValueError(f"unknown family {fam!r}")

    return ModelApi(
        cfg=cfg,
        init=functools.partial(mod.init_params, cfg),
        loss=functools.partial(mod.loss_fn, cfg),
        prefill=prefill,
        decode_step=functools.partial(mod.decode_step, cfg),
        init_cache=functools.partial(mod.init_cache, cfg),
    )
