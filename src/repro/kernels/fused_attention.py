"""Fused sparse attention: SDDMM → segment softmax → SpMM in ONE kernel,
forward AND backward, batched over heads (DESIGN.md §8–§9).

The motivating chain (graph attention / sparse transformer): for a
sparsity pattern (rows, cols) over queries Q (n_rows, d), keys
K (n_cols, d) and values V (n_cols, dv),

    s[t]   = <Q[rows[t]], K[cols[t]]> * scale (+ bias[t])   (SDDMM)
    w[t]   = softmax over {t' : rows[t'] = rows[t]}         (segment softmax)
    out[r] = Σ_{t: rows[t]=r} w[t] * V[cols[t]]             (SpMM)

Composed as separate ops this costs three HBM round trips and
materializes two (nnz,)-sized intermediates.  The fused forward makes
one pass over the nonzeros with FlashAttention-style *online
renormalization* per output row: a running row max ``m`` and denominator
``l`` carried through the race-free sequential nnz grid —

    per nnz tile i:   m_new = max(m, rowmax_i(s))          (max monoid
                      α     = exp(m - m_new)                through the
                      l     = l·α + rowsum_i(exp(s-m_new))  strategy
                      acc   = acc·α + Σ exp(s-m_new)·V      registry)
    last tile:        out   = acc / l

**Head batching.**  H heads run in ONE kernel launch: the grid is
(H, nnz_tiles, dv_tiles) and every per-head operand is flattened to a
2-D head-major buffer ((H·n_rows, d) queries, (H·n_rows, 1) row stats,
…) whose BlockSpec selects head h's slab — so the in-kernel blocks stay
2-D and the registry's scatter is reused unchanged.  The pattern
(rows/cols/bias) is shared across heads.

**Probability carry.**  The per-tile probabilities are computed once per
nnz tile (at dv step 0, together with the row statistics) and stashed in
an (nnz_tile, 1) carry block revisited by every grid step; later dv
steps of the same nnz tile read the carry instead of redoing the
d-length SDDMM dots (the PR-4 kernel recomputed scores per dv step).

**Backward.**  ``_fused_attn_bwd_kernel`` is one launch over the grid
(H, 2, nnz_tiles): the softmax backward needs the completed row dot
``δ[r] = Σ_t w_t · <dout[r], V[c_t]>`` before any dQ/dK lane can be
scattered, so the nnz grid is walked twice inside the same kernel —

    phase 0 (per tile): recompute w from the carried forward stats
                        (m, l — O(n_rows) residuals, FlashAttention
                        style), stash (w, dw) in (nnz_pad, 1) carries,
                        scatter δ (add monoid through the registry) and
                        the transpose writes dV[c] += w·dout[r];
    phase 1 (per tile): ds = w·(dw − δ[r])·scale from the carries (no
                        score recompute), scatter dQ[r] += ds·K[c] and
                        the transpose dK[c] += ds·Q[r].

All scatters run through ``group_reduce_scatter_values`` (the value-form
front of ``group_reduce_scatter``); the dK/dV transpose
scatters hand it the *cols* as segment ids — unsorted ids are correct by
the strategy contract (each transition opens a new run), just more
writebacks.

Scores, statistics and probabilities are **forced to float32** via
``common.upcast_f32`` whatever the q/k/v/dout storage dtype: the
``NEG_INF = -1e30`` masked-lane floor overflows fp16 to -inf (NaN after
the online rescale), and bf16 loses the exp cancellation.  Padded lanes
(trailing, from the nnz tile round-up) are masked by the static true
``nnz``: scores floored to NEG_INF, probabilities zeroed, so they
contribute nothing to any row or column.  Empty rows come out as exact
zeros (matching the spec oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (
    NEG_INF,
    group_reduce_scatter_values,
    pallas_call,
    upcast_f32,
)

__all__ = [
    "NEG_INF",
    "fused_sparse_attention",
    "fused_sparse_attention_bwd",
    "sparse_attention_bwd_ref",
    "sparse_attention_ref",
    "sparse_softmax_weights",
]


# ---------------------------------------------------------------------------
# Pure-JAX spec oracles
# ---------------------------------------------------------------------------


def sparse_softmax_weights(rows, cols, q, k, *, n_rows: int,
                           scale: float, bias=None):
    """Spec of the SDDMM→segment-softmax front half: the normalized
    per-nnz attention weights ``w``.  Shared by the forward oracle and
    the spec VJP, so the numerically load-bearing details (the empty-row
    isfinite guard, the 1e-30 denominator floor) cannot desynchronize
    between forward and backward.  ``bias`` is an optional (nnz,)
    additive score term (a CSR adjacency's stored values)."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    s = jnp.sum(qf[rows] * kf[cols], axis=-1) * scale  # (nnz,)
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    m = jax.ops.segment_max(s, rows, num_segments=n_rows)
    m = jnp.where(jnp.isfinite(m), m, 0.0)  # empty rows: any finite value
    p = jnp.exp(s - m[rows])
    l = jax.ops.segment_sum(p, rows, num_segments=n_rows)
    return p / jnp.maximum(l[rows], 1e-30)


def sparse_attention_ref(rows, cols, q, k, v, *, n_rows: int,
                         scale: float | None = None, bias=None):
    """Executable specification of the fused kernel (the oracle the
    kernel and its VJP are tested against).  Empty rows -> zero rows."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    w = sparse_softmax_weights(rows, cols, q, k, n_rows=n_rows,
                               scale=scale, bias=bias)
    return jax.ops.segment_sum(w[:, None] * v.astype(jnp.float32)[cols],
                               rows, num_segments=n_rows)


def sparse_attention_bwd_ref(rows, cols, q, k, v, dout, *, n_rows: int,
                             scale: float, bias=None):
    """Spec-recompute VJP (the PR-4 backward): pure-JAX softmax backward
    + SDDMM / transpose-SpMM through segment ops, recomputing the
    weights from scratch.  Returns ``(dq, dk, dv)``.  Kept as the oracle
    the fused backward kernel is tested against and as the unfused
    baseline ``beyond/fused_attention_bwd`` times."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    do = dout.astype(jnp.float32)
    w = sparse_softmax_weights(rows, cols, q, k, n_rows=n_rows,
                               scale=scale, bias=bias)  # (nnz,)
    # value gradient: transpose-SpMM of the weighted cotangent
    dv_ = jax.ops.segment_sum(w[:, None] * do[rows], cols,
                              num_segments=v.shape[0])
    # softmax backward per row: ds = w (dw - δ),  δ[r] = Σ_row w dw
    dw = jnp.sum(do[rows] * vf[cols], axis=-1)  # SDDMM(dout, V)
    delta = jax.ops.segment_sum(w * dw, rows, num_segments=n_rows)
    ds = w * (dw - delta[rows]) * scale
    dq = jax.ops.segment_sum(ds[:, None] * kf[cols], rows,
                             num_segments=n_rows)
    dk = jax.ops.segment_sum(ds[:, None] * qf[rows], cols,
                             num_segments=k.shape[0])
    return dq, dk, dv_


# ---------------------------------------------------------------------------
# The fused forward kernel
# ---------------------------------------------------------------------------


def _fused_attn_fwd_kernel(*refs, nnz: int, nnz_tile: int, scale: float,
                           group_size: int, strategy: str, has_bias: bool):
    if has_bias:
        (rows_ref, cols_ref, bias_ref, q_ref, k_ref, v_ref,
         out_ref, m_ref, l_ref, a_ref, p_ref) = refs
    else:
        (rows_ref, cols_ref, q_ref, k_ref, v_ref,
         out_ref, m_ref, l_ref, a_ref, p_ref) = refs
        bias_ref = None
    i = pl.program_id(1)  # nnz tile (sequential carry within each head)
    j = pl.program_id(2)  # dv tile (innermost)

    @pl.when((i == 0) & (j == 0))
    def _init_stats():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(i == 0)
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[...]
    cols = cols_ref[...]
    lane = i * nnz_tile + jax.lax.broadcasted_iota(
        jnp.int32, (nnz_tile,), 0)
    valid = lane < nnz

    @pl.when(j == 0)
    def _scores_and_stats():
        # SDDMM front-end, once per nnz tile: f32-forced scores, padded
        # lanes floored to NEG_INF
        q, k = upcast_f32(q_ref[...], k_ref[...])
        s = jnp.sum(jnp.take(q, rows, axis=0) * jnp.take(k, cols, axis=0),
                    axis=-1) * scale
        if bias_ref is not None:
            s = s + upcast_f32(bias_ref[...])
        s = jnp.where(valid, s, NEG_INF)
        m_old = m_ref[...]  # (R, 1)
        # running row max: the max-monoid scatter through the registry
        group_reduce_scatter_values(rows, s[:, None], m_ref, group_size,
                                    strategy, op="max")
        m_new = m_ref[...]
        alpha = jnp.where(m_old <= NEG_INF / 2, 0.0,
                          jnp.exp(m_old - m_new))  # (R, 1)
        a_ref[...] = alpha
        p = jnp.where(valid,
                      jnp.exp(jnp.where(valid, s, 0.0)
                              - jnp.take(m_new[:, 0], rows)), 0.0)
        # the probability carry: later dv steps of this nnz tile replay
        # p instead of redoing the d-length dots above
        p_ref[...] = p[:, None]
        l_ref[...] = l_ref[...] * alpha
        group_reduce_scatter_values(rows, p[:, None], l_ref, group_size,
                                    strategy)

    # SpMM back-end (every dv step): rescale the accumulator by this nnz
    # tile's α, then scatter-add the carried-probability-weighted values
    p = p_ref[...][:, 0]
    vj = upcast_f32(v_ref[...])  # (n_cols, dv_tile)
    out_ref[...] = out_ref[...] * a_ref[...]
    group_reduce_scatter_values(
        rows, p[:, None] * jnp.take(vj, cols, axis=0), out_ref, group_size,
        strategy)

    @pl.when(i == pl.num_programs(1) - 1)
    def _normalize():
        out_ref[...] = out_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "nnz", "nnz_tile", "dv_tile", "scale",
                     "group_size", "strategy"),
)
def fused_sparse_attention(rows, cols, q, k, v, *, n_rows: int, nnz: int,
                           nnz_tile: int = 256, dv_tile: int = 128,
                           scale: float, group_size: int = 32,
                           strategy: str = "segment", bias=None):
    """One-launch SDDMM→softmax→SpMM over all heads.

    Inputs pre-padded by the wrapper: rows/cols (and bias) (nnz_pad,)
    with nnz_pad % nnz_tile == 0 (``nnz`` is the true count — trailing
    pad lanes are masked in-kernel); q/k/v carry an explicit head axis —
    q (H, n_rows, d), k (H, n_kv, d), v (H, n_kv, dv_pad) with
    dv_pad % dv_tile == 0.  ``bias`` is an optional (nnz_pad,) additive
    score term shared across heads.  Returns ``(out, m, l)`` with out
    (H, n_rows, dv_pad) final and m/l (H, n_rows) the per-row softmax
    statistics — the O(H·n_rows) residuals the fused backward recomputes
    probabilities from.
    """
    nnz_pad = rows.shape[0]
    n_heads, n_q, d = q.shape
    _, n_kv, dv = v.shape
    assert nnz_pad % nnz_tile == 0 and dv % dv_tile == 0, (nnz_pad, dv)
    assert n_q == n_rows and k.shape == (n_heads, n_kv, d)
    grid = (n_heads, nnz_pad // nnz_tile, dv // dv_tile)

    # head-major flat buffers: blocks stay 2-D, head h = block-row h
    qf = q.reshape(n_heads * n_rows, d)
    kf = k.reshape(n_heads * n_kv, d)
    vf = v.reshape(n_heads * n_kv, dv)

    kernel = functools.partial(
        _fused_attn_fwd_kernel, nnz=nnz, nnz_tile=nnz_tile, scale=scale,
        group_size=group_size, strategy=strategy,
        has_bias=bias is not None)
    lane_spec = pl.BlockSpec((nnz_tile,), lambda h, i, j: (i,))
    stat_spec = pl.BlockSpec((n_rows, 1), lambda h, i, j: (h, 0))
    in_specs = [lane_spec, lane_spec]
    operands = [rows, cols]
    if bias is not None:
        in_specs.append(lane_spec)
        operands.append(bias)
    in_specs += [
        pl.BlockSpec((n_rows, d), lambda h, i, j: (h, 0)),
        pl.BlockSpec((n_kv, d), lambda h, i, j: (h, 0)),
        pl.BlockSpec((n_kv, dv_tile), lambda h, i, j: (h, j)),
    ]
    out, m, l, _alpha, _p = pallas_call(
        kernel,
        name="fused_attention_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((n_rows, dv_tile), lambda h, i, j: (h, j)),
            stat_spec, stat_spec, stat_spec,
            # the (nnz_tile, 1) probability carry: one resident block
            # revisited by every grid step
            pl.BlockSpec((nnz_tile, 1), lambda h, i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_heads * n_rows, dv), jnp.float32),
            jax.ShapeDtypeStruct((n_heads * n_rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_heads * n_rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_heads * n_rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((nnz_tile, 1), jnp.float32),
        ],
    )(*operands, qf, kf, vf)
    return (out.reshape(n_heads, n_rows, dv),
            m.reshape(n_heads, n_rows), l.reshape(n_heads, n_rows))


# ---------------------------------------------------------------------------
# The fused backward kernel
# ---------------------------------------------------------------------------


def _fused_attn_bwd_kernel(*refs, nnz: int, nnz_tile: int, scale: float,
                           group_size: int, strategy: str, has_bias: bool):
    if has_bias:
        (rows_ref, cols_ref, bias_ref, q_ref, k_ref, v_ref, do_ref,
         m_ref, l_ref,
         dq_ref, dk_ref, dv_ref, delta_ref, w_ref, dw_ref) = refs
    else:
        (rows_ref, cols_ref, q_ref, k_ref, v_ref, do_ref,
         m_ref, l_ref,
         dq_ref, dk_ref, dv_ref, delta_ref, w_ref, dw_ref) = refs
        bias_ref = None
    ph = pl.program_id(1)  # phase: 0 = δ + dV, 1 = dQ + dK
    i = pl.program_id(2)   # nnz tile

    @pl.when((ph == 0) & (i == 0))
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)
        delta_ref[...] = jnp.zeros_like(delta_ref)

    rows = rows_ref[...]
    cols = cols_ref[...]
    lane = i * nnz_tile + jax.lax.broadcasted_iota(
        jnp.int32, (nnz_tile,), 0)
    valid = lane < nnz

    @pl.when(ph == 0)
    def _delta_and_dv():
        # recompute the probabilities from the carried forward stats
        # (FlashAttention-style: O(n_rows) residuals, no (nnz,) weights
        # saved across the fwd/bwd boundary), f32-forced
        q, k, v, do = upcast_f32(q_ref[...], k_ref[...], v_ref[...],
                                 do_ref[...])
        s = jnp.sum(jnp.take(q, rows, axis=0) * jnp.take(k, cols, axis=0),
                    axis=-1) * scale
        if bias_ref is not None:
            s = s + upcast_f32(bias_ref[...])
        m_lane = jnp.take(m_ref[...][:, 0], rows)
        m_safe = jnp.where(m_lane <= NEG_INF / 2, 0.0, m_lane)
        linv = jnp.take(1.0 / jnp.maximum(l_ref[...][:, 0], 1e-30), rows)
        w = jnp.where(valid,
                      jnp.exp(jnp.where(valid, s, NEG_INF) - m_safe) * linv,
                      0.0)
        dw = jnp.sum(jnp.take(do, rows, axis=0)
                     * jnp.take(v, cols, axis=0), axis=-1)  # SDDMM(dout, V)
        # (nnz_pad, 1) carries: phase 1 replays (w, dw) with no recompute
        w_ref[...] = w[:, None]
        dw_ref[...] = dw[:, None]
        # the softmax-backward row dot δ[r] = Σ w·dw — add-monoid scatter
        group_reduce_scatter_values(rows, (w * dw)[:, None], delta_ref,
                                    group_size, strategy)
        # dV[c] += w · dout[r] — scatter-transpose (cols as segment ids)
        group_reduce_scatter_values(
            cols, w[:, None] * jnp.take(do, rows, axis=0), dv_ref,
            group_size, strategy)

    @pl.when(ph == 1)
    def _dq_and_dk():
        q, k = upcast_f32(q_ref[...], k_ref[...])
        w = w_ref[...][:, 0]
        dw = dw_ref[...][:, 0]
        ds = w * (dw - jnp.take(delta_ref[...][:, 0], rows)) * scale
        group_reduce_scatter_values(
            rows, ds[:, None] * jnp.take(k, cols, axis=0), dq_ref,
            group_size, strategy)
        # dK[c] += ds · Q[r] — scatter-transpose
        group_reduce_scatter_values(
            cols, ds[:, None] * jnp.take(q, rows, axis=0), dk_ref,
            group_size, strategy)


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "nnz", "nnz_tile", "scale", "group_size",
                     "strategy"),
)
def fused_sparse_attention_bwd(rows, cols, q, k, v, dout, m, l, *,
                               n_rows: int, nnz: int, nnz_tile: int = 256,
                               scale: float, group_size: int = 32,
                               strategy: str = "segment", bias=None):
    """One-launch fused backward: ``(dq, dk, dv)`` for all heads.

    Grid (H, 2, nnz_tiles) — the nnz grid is walked twice inside one
    kernel: phase 0 recomputes the probabilities from the forward's
    (m, l) row stats, accumulates the softmax-backward row dot δ and the
    dV transpose scatter, and stashes (w, dw) in (nnz_pad, 1) carries;
    phase 1 forms ds from the carries and scatters dQ/dK.  Layouts match
    :func:`fused_sparse_attention`: rows/cols/bias (nnz_pad,), q/k/v
    (H, n, ·), dout (H, n_rows, dv), m/l (H, n_rows) as the forward
    returned them.  No dv tiling: the backward holds whole per-head
    feature blocks, like the forward holds whole q/k blocks.
    """
    nnz_pad = rows.shape[0]
    n_heads, n_q, d = q.shape
    _, n_kv, dv = v.shape
    assert nnz_pad % nnz_tile == 0 and n_q == n_rows
    assert dout.shape == (n_heads, n_rows, dv) and m.shape == (n_heads, n_q)
    grid = (n_heads, 2, nnz_pad // nnz_tile)

    qf = q.reshape(n_heads * n_rows, d)
    kf = k.reshape(n_heads * n_kv, d)
    vf = v.reshape(n_heads * n_kv, dv)
    dof = dout.reshape(n_heads * n_rows, dv)
    mf = m.reshape(n_heads * n_rows, 1)
    lf = l.reshape(n_heads * n_rows, 1)

    kernel = functools.partial(
        _fused_attn_bwd_kernel, nnz=nnz, nnz_tile=nnz_tile, scale=scale,
        group_size=group_size, strategy=strategy,
        has_bias=bias is not None)
    lane_spec = pl.BlockSpec((nnz_tile,), lambda h, p, i: (i,))
    carry_spec = pl.BlockSpec((nnz_tile, 1), lambda h, p, i: (i, 0))
    stat_spec = pl.BlockSpec((n_rows, 1), lambda h, p, i: (h, 0))
    in_specs = [lane_spec, lane_spec]
    operands = [rows, cols]
    if bias is not None:
        in_specs.append(lane_spec)
        operands.append(bias)
    in_specs += [
        pl.BlockSpec((n_rows, d), lambda h, p, i: (h, 0)),
        pl.BlockSpec((n_kv, d), lambda h, p, i: (h, 0)),
        pl.BlockSpec((n_kv, dv), lambda h, p, i: (h, 0)),
        pl.BlockSpec((n_rows, dv), lambda h, p, i: (h, 0)),
        stat_spec, stat_spec,
    ]
    dq, dk, dv_, _delta, _w, _dw = pallas_call(
        kernel,
        name="fused_attention_bwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((n_rows, d), lambda h, p, i: (h, 0)),
            pl.BlockSpec((n_kv, d), lambda h, p, i: (h, 0)),
            pl.BlockSpec((n_kv, dv), lambda h, p, i: (h, 0)),
            stat_spec,
            carry_spec, carry_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_heads * n_rows, d), jnp.float32),
            jax.ShapeDtypeStruct((n_heads * n_kv, d), jnp.float32),
            jax.ShapeDtypeStruct((n_heads * n_kv, dv), jnp.float32),
            jax.ShapeDtypeStruct((n_heads * n_rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((nnz_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((nnz_pad, 1), jnp.float32),
        ],
    )(*operands, qf, kf, vf, dof, mf, lf)
    return (dq.reshape(n_heads, n_rows, d),
            dk.reshape(n_heads, n_kv, d),
            dv_.reshape(n_heads, n_kv, dv))
