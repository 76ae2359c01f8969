"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) in JAX.

The chunked SSD algorithm is structurally the paper's segment-group
pattern over the *time* axis: intra-chunk reduction (the masked C·B
"attention" matmul = within-group one-hot reduce) + inter-chunk carry
(the group-boundary accumulation). See DESIGN.md §6.

Projections are SPLIT (z/x/BC/dt as separate matrices rather than one
fused in_proj) so tensor parallelism can column-shard z/x/dt on the head
dim and keep the small B/C/dt replicated — the TP scheme the Mamba-2
paper itself describes. Math is identical to the fused layout.

Layout: tokens (B, S, D); SSM heads H = d_inner / head_dim (P); state N.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import init_dense, rmsnorm

# ------------------------------------------------------------------ init


def init_mixer(cfg, key):
    kz, kx, kbc, kdt, ko = jax.random.split(key, 5)
    d = cfg.d_model
    di = cfg.d_inner
    g, n, h = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_heads
    k = cfg.conv_kernel
    return {
        "z_proj": init_dense(kz, d, di, cfg.param_dtype)["w"],
        "x_proj": init_dense(kx, d, di, cfg.param_dtype)["w"],
        "bc_proj": init_dense(kbc, d, 2 * g * n, cfg.param_dtype)["w"],
        "dt_proj": init_dense(kdt, d, h, cfg.param_dtype)["w"],
        "conv_x_w": (jax.random.normal(key, (k, di)) * k ** -0.5
                     ).astype(cfg.param_dtype),
        "conv_x_b": jnp.zeros((di,), cfg.param_dtype),
        "conv_bc_w": (jax.random.normal(kbc, (k, 2 * g * n)) * k ** -0.5
                      ).astype(cfg.param_dtype),
        "conv_bc_b": jnp.zeros((2 * g * n,), cfg.param_dtype),
        "A_log": jnp.zeros((h,), jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": jnp.zeros((di,), cfg.param_dtype),
        "out_proj": init_dense(ko, di, d, cfg.param_dtype,
                               scale=di ** -0.5)["w"],
    }


# ------------------------------------------------------------------- ssd


def _conv1d_causal(x, w, b):
    """Depthwise causal conv. x: (B, S, C); w: (K, C); b: (C,)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp.astype(jnp.float32), w[:, None, :].astype(jnp.float32),
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1])
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def ssd_chunked(x, dt, a, b_in, c_in, chunk, d_skip, init_state=None):
    """Chunked SSD scan.

    x: (B, S, H, P); dt: (B, S, H) (already softplus'd); a: (H,) negative;
    b_in/c_in: (B, S, G, N). Returns (y (B, S, H, P), final_state
    (B, H, N, P)).
    """
    bs, s0, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    hpg = h // g
    q = min(chunk, s0)
    pad = (-s0) % q
    if pad:
        # zero extension along time: dt=0 -> decay 1, contribution 0, so
        # both outputs and the final state are unaffected.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_in = jnp.pad(b_in, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_in = jnp.pad(c_in, ((0, 0), (0, pad), (0, 0), (0, 0)))
    s = s0 + pad
    nc = s // q

    xf = x.astype(jnp.float32).reshape(bs, nc, q, h, p)
    dtc = dt.astype(jnp.float32).reshape(bs, nc, q, h)
    da = (dtc * a).astype(jnp.float32)  # (B,nc,Q,H)
    bh = jnp.repeat(b_in.astype(jnp.float32).reshape(bs, nc, q, g, n),
                    hpg, axis=3)  # (B,nc,Q,H,N)
    ch = jnp.repeat(c_in.astype(jnp.float32).reshape(bs, nc, q, g, n),
                    hpg, axis=3)

    seg = jnp.cumsum(da, axis=2)  # (B,nc,Q,H) inclusive
    # intra-chunk ("diagonal block"): masked attention-like matmul
    cb = jnp.einsum("bnihe,bnjhe->bnijh", ch, bh)  # (B,nc,Q,Q,H)
    decay = seg[:, :, :, None, :] - seg[:, :, None, :, :]
    mask = (jnp.arange(q)[:, None] >= jnp.arange(q)[None, :])
    l_mat = jnp.where(mask[None, None, ..., None], jnp.exp(decay), 0.0)
    w_mat = cb * l_mat * dtc[:, :, None, :, :]
    y_diag = jnp.einsum("bnijh,bnjhp->bnihp", w_mat, xf)

    # chunk states + inter-chunk carry (the group-boundary accumulation)
    seg_end = seg[:, :, -1:, :]  # (B,nc,1,H)
    sdecay = jnp.exp(seg_end - seg)  # (B,nc,Q,H)
    states = jnp.einsum("bnqh,bnqhe,bnqhp->bnhep",
                        dtc * sdecay, bh, xf)  # (B,nc,H,N,P)
    chunk_decay = jnp.exp(seg_end[:, :, 0, :])  # (B,nc,H)

    def scan_fn(carry, inp):
        st, cd = inp
        return carry * cd[..., None, None] + st, carry

    init = (jnp.zeros((bs, h, n, p), jnp.float32)
            if init_state is None else init_state.astype(jnp.float32))
    final, prev = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev = jnp.moveaxis(prev, 0, 1)  # (B,nc,H,N,P) state before each chunk

    y_off = jnp.einsum("bnqhe,bnhep,bnqh->bnqhp", ch, prev, jnp.exp(seg))
    y = (y_diag + y_off).reshape(bs, s, h, p)
    y = y + d_skip[None, None, :, None] * x.astype(jnp.float32)
    return y[:, :s0].astype(x.dtype), final


# ----------------------------------------------------------------- block


def _project(cfg, p, x):
    """x (..., D) -> z (..., di), xs (..., di), bc (..., 2GN), dt (..., H)
    pre-conv/pre-activation."""
    z = jnp.einsum("...d,df->...f", x, p["z_proj"].astype(x.dtype))
    xs = jnp.einsum("...d,df->...f", x, p["x_proj"].astype(x.dtype))
    bc = jnp.einsum("...d,df->...f", x, p["bc_proj"].astype(x.dtype))
    dt = jnp.einsum("...d,df->...f", x, p["dt_proj"].astype(x.dtype))
    return z, xs, bc, dt


def mixer_fwd(cfg, p, x, init_state=None, return_state=False):
    """Full-sequence mamba2 mixer. x: (B, S, D) -> (B, S, D)."""
    bs, s, _ = x.shape
    h, pd, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    z, xs_raw, bc_raw, dt = _project(cfg, p, x)
    xs = jax.nn.silu(_conv1d_causal(xs_raw, p["conv_x_w"], p["conv_x_b"]))
    bc = jax.nn.silu(_conv1d_causal(bc_raw, p["conv_bc_w"], p["conv_bc_b"]))
    xh = xs.reshape(bs, s, h, pd)
    b_in = bc[..., : g * n].reshape(bs, s, g, n)
    c_in = bc[..., g * n:].reshape(bs, s, g, n)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    y, final = ssd_chunked(xh, dt, a, b_in, c_in, cfg.ssm_chunk, p["D"],
                           init_state)
    y = y.reshape(bs, s, cfg.d_inner)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                p["norm"])
    out = jnp.einsum("bsf,fd->bsd", y, p["out_proj"].astype(y.dtype))
    if return_state:
        kk = cfg.conv_kernel - 1
        pad_x = jnp.zeros((bs, max(0, kk - s), xs_raw.shape[-1]), x.dtype)
        pad_bc = jnp.zeros((bs, max(0, kk - s), bc_raw.shape[-1]), x.dtype)
        st = {
            "ssm": final,
            "conv_x": jnp.concatenate([pad_x, xs_raw[:, -kk:]], axis=1),
            "conv_bc": jnp.concatenate([pad_bc, bc_raw[:, -kk:]], axis=1),
        }
        return out, st
    return out


def init_mixer_cache(cfg, batch_size, dtype=None):
    dtype = dtype or cfg.compute_dtype
    g, n = cfg.ssm_groups, cfg.ssm_state
    kk = cfg.conv_kernel - 1
    return {
        "ssm": jnp.zeros((batch_size, cfg.ssm_heads, n, cfg.ssm_head_dim),
                         jnp.float32),
        "conv_x": jnp.zeros((batch_size, kk, cfg.d_inner), dtype),
        "conv_bc": jnp.zeros((batch_size, kk, 2 * g * n), dtype),
    }


def _conv_step(window, new, w, b):
    """One causal-conv step. window (B, K-1, C), new (B, C) -> (out (B, C),
    new window)."""
    full = jnp.concatenate([window, new[:, None, :]], axis=1)
    out = jnp.einsum("bkc,kc->bc", full.astype(jnp.float32),
                     w.astype(jnp.float32)) + b.astype(jnp.float32)
    return out, full[:, 1:]


def mixer_decode(cfg, p, cache, x):
    """Single-token step. x: (B, D) -> (B, D), new cache."""
    bs, _ = x.shape
    h, pd, n, g = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    z, xs_raw, bc_raw, dt = _project(cfg, p, x)
    cx, new_conv_x = _conv_step(cache["conv_x"], xs_raw,
                                p["conv_x_w"], p["conv_x_b"])
    cbc, new_conv_bc = _conv_step(cache["conv_bc"], bc_raw,
                                  p["conv_bc_w"], p["conv_bc_b"])
    xs = jax.nn.silu(cx).astype(x.dtype).reshape(bs, h, pd)
    bc = jax.nn.silu(cbc).astype(x.dtype)
    b_in = jnp.repeat(bc[..., : g * n].reshape(bs, g, n), h // g, axis=1)
    c_in = jnp.repeat(bc[..., g * n:].reshape(bs, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, H)
    a = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt * a)  # (B, H)
    state = cache["ssm"] * decay[..., None, None] + jnp.einsum(
        "bh,bhe,bhp->bhep", dt, b_in.astype(jnp.float32),
        xs.astype(jnp.float32))
    y = jnp.einsum("bhe,bhep->bhp", c_in.astype(jnp.float32), state)
    y = y + p["D"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bs, cfg.d_inner)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)), p["norm"])
    out = jnp.einsum("bf,fd->bd", y.astype(x.dtype),
                     p["out_proj"].astype(x.dtype))
    return out, {"ssm": state, "conv_x": new_conv_x, "conv_bc": new_conv_bc}
