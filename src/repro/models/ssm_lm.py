"""Mamba-2 language model (attention-free): x += mixer(norm(x)) per layer."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import (apply_norm, embed, init_embedding, init_norm,
                     lm_loss_from_features, unembed)
from .mamba2 import (init_mixer, init_mixer_cache, mixer_decode, mixer_fwd)


def init_layer(cfg, key):
    return {"ln": init_norm(cfg, cfg.d_model), "mixer": init_mixer(cfg, key)}


def init_params(cfg, key):
    ke, kl = jax.random.split(key)
    layers = jax.vmap(lambda k: init_layer(cfg, k))(
        jax.random.split(kl, cfg.n_layers))
    return {
        "embed": init_embedding(ke, cfg.vocab_size, cfg.d_model,
                                cfg.param_dtype),
        "layers": layers,
        "final_norm": init_norm(cfg, cfg.d_model),
    }


def forward_features(cfg, params, tokens, ctx=None):
    del ctx
    x = embed(params["embed"], tokens).astype(cfg.compute_dtype)

    def layer(p_l, x):
        return x + mixer_fwd(cfg, p_l["mixer"], apply_norm(cfg, p_l["ln"], x))

    if cfg.remat:
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)

    def step(x, p_l):
        return layer(p_l, x), None

    x, _ = jax.lax.scan(step, x, params["layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    return x


def forward(cfg, params, tokens, ctx=None):
    x = forward_features(cfg, params, tokens, ctx)
    return unembed(params["embed"], x), jnp.zeros((), jnp.float32)


def loss_fn(cfg, params, batch, ctx=None):
    x = forward_features(cfg, params, batch["tokens"], ctx)
    return lm_loss_from_features(params["embed"], x[:, :-1],
                                 batch["tokens"][:, 1:], batch.get("mask"))


def init_cache(cfg, batch_size, max_len, dtype=None):
    del max_len  # state models have O(1) cache
    one = init_mixer_cache(cfg, batch_size, dtype)
    return {
        "layers": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), one),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(cfg, params, tokens, max_len, ctx=None):
    del max_len, ctx
    x = embed(params["embed"], tokens).astype(cfg.compute_dtype)

    def step(x, p_l):
        h = apply_norm(cfg, p_l["ln"], x)
        out, st = mixer_fwd(cfg, p_l["mixer"], h, return_state=True)
        return x + out, st

    x, states = jax.lax.scan(step, x, params["layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(params["embed"], x[:, -1])
    return logits, {"layers": states,
                    "pos": jnp.asarray(tokens.shape[1], jnp.int32)}


def decode_step(cfg, params, cache, tokens, ctx=None):
    del ctx
    x = embed(params["embed"], tokens).astype(cfg.compute_dtype)  # (B, D)

    def step(x, inp):
        p_l, cache_l = inp
        h = apply_norm(cfg, p_l["ln"], x)
        out, new_cache = mixer_decode(cfg, p_l["mixer"], cache_l, h)
        return x + out, new_cache

    x, new_layers = jax.lax.scan(step, x, (params["layers"],
                                           cache["layers"]))
    x = apply_norm(cfg, params["final_norm"], x)
    return unembed(params["embed"], x), {"layers": new_layers,
                                         "pos": cache["pos"] + 1}
