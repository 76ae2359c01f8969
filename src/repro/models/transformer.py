"""Decoder-only transformer LM (dense GQA + optional MoE FFN).

Layers are stacked (leading L dim) and applied with ``jax.lax.scan`` to
keep the HLO size mesh-compile friendly; ``cfg.remat`` wraps the layer in
``jax.checkpoint``. Covers families: dense, moe, and the text towers of
vlm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import decode_attention, flash_attention
from .layers import (apply_dense, apply_mlp, apply_norm, apply_rope,
                     embed, init_dense, init_embedding,
                     init_mlp, init_norm, lm_loss_from_features,
                     rmsnorm, seq_shard, seq_unshard, unembed)
from .moe import apply_moe, init_moe

AUX_WEIGHT = 0.01


# ------------------------------------------------------------------ init


def init_attn(cfg, key):
    kq, kk, kv, ko = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": init_dense(kq, d, cfg.attn_dim, cfg.param_dtype, bias=cfg.qkv_bias),
        "wk": init_dense(kk, d, cfg.kv_dim, cfg.param_dtype, bias=cfg.qkv_bias),
        "wv": init_dense(kv, d, cfg.kv_dim, cfg.param_dtype, bias=cfg.qkv_bias),
        "wo": init_dense(ko, cfg.attn_dim, d, cfg.param_dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((cfg.d_head,), cfg.param_dtype)
        p["k_norm"] = jnp.zeros((cfg.d_head,), cfg.param_dtype)
    return p


def init_layer(cfg, key):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": init_norm(cfg, cfg.d_model),
        "attn": init_attn(cfg, k1),
        "ln2": init_norm(cfg, cfg.d_model),
    }
    if cfg.family == "moe":
        p["moe"] = init_moe(cfg, k2)
    else:
        p["mlp"] = init_mlp(cfg, k2)
    return p


def init_params(cfg, key):
    ke, kl, kf = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(cfg, k))(layer_keys)
    return {
        "embed": init_embedding(ke, cfg.vocab_size, cfg.d_model,
                                cfg.param_dtype),
        "layers": layers,
        "final_norm": init_norm(cfg, cfg.d_model),
    }


# -------------------------------------------------------------- forward


def _qkv(cfg, p, x, positions):
    b, s, _ = x.shape
    q = apply_dense(p["wq"], x).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = apply_dense(p["wk"], x).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = apply_dense(p["wv"], x).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_block(cfg, p, x, positions, causal=True):
    q, k, v = _qkv(cfg, p, x, positions)
    if cfg.seq_parallel_attn:
        # q stays seq-sharded (local S/tp rows per chip); K/V all-gather
        # over 'model' (small GQA tensors). q_chunk = full seq so the
        # chunk reshape never crosses the shard layout.
        q = seq_shard(cfg, q)
        k = seq_unshard(cfg, k)
        v = seq_unshard(cfg, v)
        q_chunk = q.shape[1]
    else:
        q_chunk = cfg.q_chunk
    o = flash_attention(q, k, v, causal, q_chunk, cfg.kv_chunk)
    b, s, _, _ = o.shape
    return apply_dense(p["wo"], o.reshape(b, s, cfg.attn_dim)), (k, v)


def ffn_block(cfg, p, x, ctx=None):
    if cfg.family == "moe":
        b, s, d = x.shape
        out, aux = apply_moe(cfg, p["moe"], x.reshape(b * s, d), ctx)
        return out.reshape(b, s, d), aux
    return apply_mlp(cfg, p["mlp"], x), jnp.zeros((), jnp.float32)


def layer_fwd(cfg, p, x, positions, ctx=None):
    x = seq_shard(cfg, x)  # pin the residual stream (no-op unless SP)
    a, _ = attn_block(cfg, p["attn"], apply_norm(cfg, p["ln1"], x), positions)
    x = seq_shard(cfg, x + a)
    f, aux = ffn_block(cfg, p, apply_norm(cfg, p["ln2"], x), ctx)
    return seq_shard(cfg, x + f), aux


def forward_features(cfg, params, tokens, ctx=None, inputs_embeds=None):
    """tokens (B, S) -> (final features (B, S, D), aux loss)."""
    x = embed(params["embed"], tokens) if inputs_embeds is None else inputs_embeds
    x = x.astype(cfg.compute_dtype)
    # under SP: pin the embedding output (and its cotangent) unsharded —
    # the table-scatter vjp miscomputes with a seq-sharded cotangent
    # (XLA SPMD uneven/masked scatter issue); layers reshard right after.
    x = seq_unshard(cfg, x)
    positions = jnp.arange(x.shape[1])

    # ctx is closure-bound (not a positional arg): jax.checkpoint treats
    # positional args as arrays to differentiate through.
    def layer(p_l, x, positions):
        return layer_fwd(cfg, p_l, x, positions, ctx)

    if cfg.remat:
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)

    def step(x, p_l):
        x, aux = layer(p_l, x, positions)
        return x, aux

    x, auxs = jax.lax.scan(step, x, params["layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    # under SP, hand the loss head an unsharded-seq tensor: the uneven
    # x[:, :-1] slice of a seq-sharded dim miscomputes the embed grad
    # (XLA SPMD uneven-shard scatter); one (B,S,D) all-gather is cheap.
    x = seq_unshard(cfg, x)
    return x, jnp.sum(auxs)


def forward(cfg, params, tokens, ctx=None, inputs_embeds=None):
    """tokens (B, S) -> logits (B, S, V)."""
    x, aux = forward_features(cfg, params, tokens, ctx, inputs_embeds)
    return unembed(params["embed"], x), aux


def loss_fn(cfg, params, batch, ctx=None):
    x, aux = forward_features(cfg, params, batch["tokens"], ctx)
    loss = lm_loss_from_features(params["embed"], x[:, :-1],
                                 batch["tokens"][:, 1:], batch.get("mask"))
    return loss + AUX_WEIGHT * aux


# --------------------------------------------------------------- serving


def init_cache(cfg, batch_size, max_len, dtype=None):
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, batch_size, max_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def prefill(cfg, params, tokens, max_len, ctx=None, inputs_embeds=None):
    """Run the full prompt, return (last-token logits, populated cache)."""
    x = (embed(params["embed"], tokens)
         if inputs_embeds is None else inputs_embeds)
    x = x.astype(cfg.compute_dtype)
    b, s = x.shape[:2]
    positions = jnp.arange(s)

    def step(x, p_l):
        a, (k, v) = attn_block(cfg, p_l["attn"],
                               apply_norm(cfg, p_l["ln1"], x), positions)
        x = x + a
        f, _ = ffn_block(cfg, p_l, apply_norm(cfg, p_l["ln2"], x), ctx)
        return x + f, (k, v)

    x, (ks, vs) = jax.lax.scan(step, x, params["layers"])
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(params["embed"], x[:, -1])
    pad = max_len - s
    cache = {
        "k": jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "pos": jnp.asarray(s, jnp.int32),
    }
    return logits, cache


def decode_step(cfg, params, cache, tokens, ctx=None):
    """One decode step. tokens (B,) int32; cache from init_cache/prefill.
    Returns (logits (B, V), new cache)."""
    if cfg.decode_inplace_cache:
        return _decode_step_inplace(cfg, params, cache, tokens, ctx)
    pos = cache["pos"]
    x = embed(params["embed"], tokens)[:, None, :].astype(cfg.compute_dtype)
    positions = pos[None, None].astype(jnp.float32) + jnp.zeros(
        (x.shape[0], 1), jnp.float32)

    def step(x, inp):
        p_l, k_c, v_c = inp
        h = apply_norm(cfg, p_l["ln1"], x)
        q, k, v = _qkv(cfg, p_l["attn"], h, positions)
        k_c = jax.lax.dynamic_update_slice(k_c, k, (0, pos, 0, 0))
        v_c = jax.lax.dynamic_update_slice(v_c, v, (0, pos, 0, 0))
        o = decode_attention(q[:, 0], k_c, v_c, pos)
        a = apply_dense(p_l["attn"]["wo"],
                        o.reshape(x.shape[0], 1, cfg.attn_dim)[:, 0])
        x = x + a[:, None, :]
        f, _ = ffn_block(cfg, p_l, apply_norm(cfg, p_l["ln2"], x), ctx)
        return x + f, (k_c, v_c)

    x, (ks, vs) = jax.lax.scan(step, x, (params["layers"], cache["k"],
                                         cache["v"]))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(params["embed"], x[:, 0])
    return logits, {"k": ks, "v": vs, "pos": pos + 1}


def _tree_index(tree, i):
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


def _decode_step_inplace(cfg, params, cache, tokens, ctx=None):
    """Decode with the stacked caches as fori_loop carry updated via
    dynamic-update-slice — XLA forwards the buffer in place instead of
    double-buffering a second full cache through scan ys."""
    pos = cache["pos"]
    b = tokens.shape[0]
    x = embed(params["embed"], tokens)[:, None, :].astype(cfg.compute_dtype)
    positions = pos[None, None].astype(jnp.float32) + jnp.zeros(
        (b, 1), jnp.float32)

    def body(l, carry):
        x, kc, vc = carry
        p_l = _tree_index(params["layers"], l)
        h = apply_norm(cfg, p_l["ln1"], x)
        q, k, v = _qkv(cfg, p_l["attn"], h, positions)
        kc = jax.lax.dynamic_update_slice(kc, k[None].astype(kc.dtype),
                                          (l, 0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v[None].astype(vc.dtype),
                                          (l, 0, pos, 0, 0))
        kl = jax.lax.dynamic_index_in_dim(kc, l, 0, keepdims=False)
        vl = jax.lax.dynamic_index_in_dim(vc, l, 0, keepdims=False)
        o = decode_attention(q[:, 0], kl, vl, pos)
        a = apply_dense(p_l["attn"]["wo"], o.reshape(b, cfg.attn_dim))
        x = x + a[:, None, :]
        f, _ = ffn_block(cfg, p_l, apply_norm(cfg, p_l["ln2"], x), ctx)
        return (x + f, kc, vc)

    x, kc, vc = jax.lax.fori_loop(
        0, cfg.n_layers, body, (x, cache["k"], cache["v"]))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed(params["embed"], x[:, 0])
    return logits, {"k": kc, "v": vc, "pos": pos + 1}
