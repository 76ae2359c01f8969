"""Attention: GQA flash attention (chunked, custom-VJP) + decode step.

``flash_attention`` never materializes the (Sq × Skv) score matrix: forward
runs a scan over KV chunks with online softmax; backward recomputes
probabilities per chunk from the saved (o, lse) — O(S·D) residual memory
instead of O(S²).

Layout: q (B, Sq, H, Dh), k/v (B, Skv, K, Dh) with H = K·G (GQA).
Internally (B, K, G, S, Dh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels.common import NEG_INF  # shared masked-lane floor


def _chunk(x, axis, size):
    """Split axis into (n_chunks, size) and move n_chunks to the front."""
    n = x.shape[axis] // size
    shape = x.shape[:axis] + (n, size) + x.shape[axis + 1:]
    x = x.reshape(shape)
    return jnp.moveaxis(x, axis, 0)


def _mask(qpos, kpos, causal):
    if not causal:
        return None
    return qpos[:, None] >= kpos[None, :]  # (qc, kc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, q_chunk: int = 512,
                    kv_chunk: int = 512):
    o, _ = _flash_fwd_impl(q, k, v, causal, q_chunk, kv_chunk)
    return o


def _pad_seq(x, chunk, axis):
    s = x.shape[axis]
    pad = (-s) % chunk
    if pad:
        cfgpad = [(0, 0)] * x.ndim
        cfgpad[axis] = (0, pad)
        x = jnp.pad(x, cfgpad)
    return x, s


def _flash_fwd_impl(q, k, v, causal, q_chunk, kv_chunk):
    b, sq0, h, dh = q.shape
    _, skv0, kh, _ = k.shape
    g = h // kh
    q_chunk = min(q_chunk, sq0) if sq0 % min(q_chunk, sq0) == 0 else sq0
    kv_chunk = min(kv_chunk, skv0) if skv0 % min(kv_chunk, skv0) == 0 else skv0

    qi = jnp.moveaxis(q.reshape(b, sq0, kh, g, dh), 1, 3)  # (B,K,G,Sq,Dh)
    ki = jnp.moveaxis(k, 1, 2)  # (B,K,Skv,Dh)
    vi = jnp.moveaxis(v, 1, 2)
    scale = dh ** -0.5

    qcs = _chunk(qi, 3, q_chunk)      # (nq, B,K,G,qc,Dh)
    kcs = _chunk(ki, 2, kv_chunk)     # (nk, B,K,kc,Dh)
    vcs = _chunk(vi, 2, kv_chunk)
    nq, nk = qcs.shape[0], kcs.shape[0]

    def q_step(_, qin):
        qc, iq = qin  # (B,K,G,qc,Dh), scalar chunk index
        qpos = iq * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, kin):
            m, l, acc = carry
            kc, vc, ik = kin
            kpos = ik * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qc.astype(jnp.float32),
                           kc.astype(jnp.float32)) * scale
            if causal:
                s = jnp.where(_mask(qpos, kpos, True)[None, None, None], s,
                              NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p, vc.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((b, kh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kh, g, q_chunk, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (kcs, vcs, jnp.arange(nk)))
        l_safe = jnp.maximum(l, 1e-30)
        o = acc / l_safe[..., None]
        lse = m + jnp.log(l_safe)
        return None, (o.astype(q.dtype), lse)

    _, (ocs, lses) = jax.lax.scan(q_step, None, (qcs, jnp.arange(nq)))
    # (nq, B,K,G,qc,Dh) -> (B, Sq, H, Dh)
    o = jnp.moveaxis(ocs, 0, 3).reshape(b, kh, g, sq0, dh)
    o = jnp.moveaxis(o, 3, 1).reshape(b, sq0, h, dh)
    lse = jnp.moveaxis(lses, 0, 3).reshape(b, kh, g, sq0)
    return o, lse


def _flash_fwd(q, k, v, causal, q_chunk, kv_chunk):
    o, lse = _flash_fwd_impl(q, k, v, causal, q_chunk, kv_chunk)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, q_chunk, kv_chunk, res, do):
    q, k, v, o, lse = res
    b, sq0, h, dh = q.shape
    _, skv0, kh, _ = k.shape
    g = h // kh
    q_chunk = min(q_chunk, sq0) if sq0 % min(q_chunk, sq0) == 0 else sq0
    kv_chunk = min(kv_chunk, skv0) if skv0 % min(kv_chunk, skv0) == 0 else skv0
    scale = dh ** -0.5

    qi = jnp.moveaxis(q.reshape(b, sq0, kh, g, dh), 1, 3).astype(jnp.float32)
    ki = jnp.moveaxis(k, 1, 2).astype(jnp.float32)
    vi = jnp.moveaxis(v, 1, 2).astype(jnp.float32)
    oi = jnp.moveaxis(do.reshape(b, sq0, kh, g, dh), 1, 3).astype(jnp.float32)
    ooi = jnp.moveaxis(o.reshape(b, sq0, kh, g, dh), 1, 3).astype(jnp.float32)
    delta = jnp.sum(oi * ooi, axis=-1)  # (B,K,G,Sq)

    qcs = _chunk(qi, 3, q_chunk)
    docs = _chunk(oi, 3, q_chunk)
    lcs = _chunk(lse, 3, q_chunk)
    dcs = _chunk(delta, 3, q_chunk)
    kcs = _chunk(ki, 2, kv_chunk)
    vcs = _chunk(vi, 2, kv_chunk)
    nq, nk = qcs.shape[0], kcs.shape[0]

    def q_step(carry, qin):
        dk_all, dv_all = carry  # (nk, B,K,kc,Dh) each
        qc, doc, lc, dc, iq = qin
        qpos = iq * q_chunk + jnp.arange(q_chunk)

        def kv_step(dq_c, kin):
            kc, vc, dk_c, dv_c, ik = kin
            kpos = ik * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qc, kc) * scale
            if causal:
                s = jnp.where(_mask(qpos, kpos, True)[None, None, None], s,
                              NEG_INF)
            p = jnp.exp(s - lc[..., None])  # (B,K,G,qc,kc)
            dp = jnp.einsum("bkgqd,bkcd->bkgqc", doc, vc)
            ds = p * (dp - dc[..., None]) * scale
            dq_c = dq_c + jnp.einsum("bkgqc,bkcd->bkgqd", ds, kc)
            dk_c = dk_c + jnp.einsum("bkgqc,bkgqd->bkcd", ds, qc)
            dv_c = dv_c + jnp.einsum("bkgqc,bkgqd->bkcd", p, doc)
            return dq_c, (dk_c, dv_c)

        dq0 = jnp.zeros_like(qc)
        dq_c, (dk_all, dv_all) = jax.lax.scan(
            kv_step, dq0, (kcs, vcs, dk_all, dv_all, jnp.arange(nk)))
        return (dk_all, dv_all), dq_c

    dk0 = jnp.zeros((nk, b, kh, kv_chunk, dh), jnp.float32)
    dv0 = jnp.zeros_like(dk0)
    (dk_all, dv_all), dq_cs = jax.lax.scan(
        q_step, (dk0, dv0), (qcs, docs, lcs, dcs, jnp.arange(nq)))

    dq = jnp.moveaxis(dq_cs, 0, 3).reshape(b, kh, g, sq0, dh)
    dq = jnp.moveaxis(dq, 3, 1).reshape(b, sq0, h, dh).astype(q.dtype)
    dk = jnp.moveaxis(dk_all, 0, 2).reshape(b, kh, skv0, dh)
    dk = jnp.moveaxis(dk, 2, 1).astype(k.dtype)
    dv = jnp.moveaxis(dv_all, 0, 2).reshape(b, kh, skv0, dh)
    dv = jnp.moveaxis(dv, 2, 1).astype(v.dtype)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)


def graph_attention(adj, q, k, v, *, schedule=None, scale=None,
                    score="dot", slope=0.2, keep=None):
    """Sparse (graph) attention over an adjacency pattern through the
    fused score→softmax→SpMM kernels (``repro.sparse.sparse_attention``),
    fused in both directions.  ``score='additive'`` is GAT's
    ``LeakyReLU_slope(q_i + k_j)`` over per-node, per-head terms of width
    1; ``keep`` an optional (nnz, H) mask on the normalised coefficients.

    Single-head: q (n_rows, d), k/v (n_cols, d/dv).  Multi-head: q
    (n_rows, H, d) with k/v (n_cols, H, ·) — heads share the sparsity
    pattern and ALL run in one kernel launch (the head axis is folded
    into the fused kernel's grid; no Python head loop).  A CSR
    adjacency's stored values act as an additive score bias (edge
    features); see ``repro.sparse.sparse_attention``.
    """
    from ..sparse import sparse_attention

    return sparse_attention(adj, q, k, v, schedule=schedule, scale=scale,
                            score=score, slope=slope, keep=keep)


def attention_ref(q, k, v, causal=True):
    """Naive reference for tests."""
    b, sq, h, dh = q.shape
    kh = k.shape[2]
    g = h // kh
    qi = q.reshape(b, sq, kh, g, dh)
    s = jnp.einsum("bqkgd,bckd->bkgqc", qi.astype(jnp.float32),
                   k.astype(jnp.float32)) * dh ** -0.5
    if causal:
        mask = jnp.arange(sq)[:, None] >= jnp.arange(k.shape[1])[None, :]
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bckd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, dh).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos):
    """Single-token decode. q: (B, H, Dh); caches: (B, S, K, Dh); pos: ()
    current position (tokens at index <= pos are valid).

    Caches stay in their storage dtype; f32 happens in the MXU accumulator
    (preferred_element_type), not as materialized copies.
    """
    b, s, kh, dh = k_cache.shape
    g = q.shape[1] // kh
    qi = q.reshape(b, kh, g, dh)
    scores = jnp.einsum("bkgd,bskd->bkgs", qi, k_cache,
                        preferred_element_type=jnp.float32) * dh ** -0.5
    valid = jnp.arange(s)[None, None, None, :] <= pos
    scores = jnp.where(valid, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, q.shape[1], dh).astype(q.dtype)
