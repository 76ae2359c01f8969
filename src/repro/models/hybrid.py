"""Hymba-style hybrid LM: parallel attention + mamba heads per layer
(arXiv:2411.13676), then an MLP block.

Fusion follows Hymba's normalized weighted sum (learned per-layer scalars
over per-branch RMS-normalized outputs). Meta-tokens and the sliding-window
mix are not modeled (noted in DESIGN.md §6).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import decode_attention
from .layers import (apply_dense, apply_mlp, apply_norm, embed,
                     init_embedding, init_mlp, init_norm,
                     lm_loss_from_features, rmsnorm, unembed)
from .mamba2 import init_mixer, init_mixer_cache, mixer_decode, mixer_fwd
from .transformer import _qkv, attn_block, init_attn


def init_layer(cfg, key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": init_norm(cfg, cfg.d_model),
        "attn": init_attn(cfg, k1),
        "mixer": init_mixer(cfg, k2),
        "beta_attn": jnp.ones((), jnp.float32),
        "beta_ssm": jnp.ones((), jnp.float32),
        "ln2": init_norm(cfg, cfg.d_model),
        "mlp": init_mlp(cfg, k3),
    }


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


def _fuse(p_l, a, m):
    af = rmsnorm(a, jnp.zeros((a.shape[-1],), a.dtype))
    mf = rmsnorm(m, jnp.zeros((m.shape[-1],), m.dtype))
    return 0.5 * (p_l["beta_attn"] * af.astype(jnp.float32)
                  + p_l["beta_ssm"] * mf.astype(jnp.float32)).astype(a.dtype)


def forward_features(cfg, params, tokens, ctx=None):
    del ctx
    x = embed(params["embed"], tokens).astype(cfg.compute_dtype)
    positions = jnp.arange(x.shape[1])

    def layer(p_l, x):
        h = apply_norm(cfg, p_l["ln1"], x)
        a, _ = attn_block(cfg, p_l["attn"], h, positions)
        m = mixer_fwd(cfg, p_l["mixer"], h)
        x = x + _fuse(p_l, a, m)
        return x + apply_mlp(cfg, p_l["mlp"], apply_norm(cfg, p_l["ln2"], x))

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
    dtype = dtype or cfg.compute_dtype
    kv_shape = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads, cfg.d_head)
    one = init_mixer_cache(cfg, batch_size, dtype)
    return {
        "k": jnp.zeros(kv_shape, dtype),
        "v": jnp.zeros(kv_shape, dtype),
        "mixer": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape), one),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(cfg, params, tokens, max_len, ctx=None):
    del ctx
    b, s = tokens.shape
    x = embed(params["embed"], tokens).astype(cfg.compute_dtype)
    positions = jnp.arange(s)

    def step(x, p_l):
        h = apply_norm(cfg, p_l["ln1"], x)
        a, (k, v) = attn_block(cfg, p_l["attn"], h, positions)
        m, st = mixer_fwd(cfg, p_l["mixer"], h, return_state=True)
        x = x + _fuse(p_l, a, m)
        x = x + apply_mlp(cfg, p_l["mlp"], apply_norm(cfg, p_l["ln2"], x))
        return x, (k, v, st)

    x, (ks, vs, states) = jax.lax.scan(step, x, params["layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(params["embed"], x[:, -1])
    pad = max_len - s
    return logits, {
        "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "mixer": states,
        "pos": jnp.asarray(s, jnp.int32),
    }


def decode_step(cfg, params, cache, tokens, ctx=None):
    del ctx
    pos = cache["pos"]
    x = embed(params["embed"], tokens)[:, None, :].astype(cfg.compute_dtype)
    positions = pos[None, None].astype(jnp.float32) + jnp.zeros(
        (x.shape[0], 1), jnp.float32)

    def step(x, inp):
        p_l, k_c, v_c, mix_c = inp
        h = apply_norm(cfg, p_l["ln1"], x)
        q, k, v = _qkv(cfg, p_l["attn"], h, positions)
        k_c = jax.lax.dynamic_update_slice(k_c, k, (0, pos, 0, 0))
        v_c = jax.lax.dynamic_update_slice(v_c, v, (0, pos, 0, 0))
        o = decode_attention(q[:, 0], k_c, v_c, pos)
        a = apply_dense(p_l["attn"]["wo"],
                        o.reshape(x.shape[0], cfg.attn_dim))[:, None, :]
        m, new_mix = mixer_decode(cfg, p_l["mixer"], mix_c, h[:, 0])
        x = x + _fuse(p_l, a, m[:, None, :])
        x = x + apply_mlp(cfg, p_l["mlp"], apply_norm(cfg, p_l["ln2"], x))
        return x, (k_c, v_c, new_mix)

    x, (ks, vs, mixs) = jax.lax.scan(
        step, x, (params["layers"], cache["k"], cache["v"], cache["mixer"]))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(params["embed"], x[:, 0])
    return logits, {"k": ks, "v": vs, "mixer": mixs, "pos": pos + 1}
