"""Fused sparse attention: SDDMM → segment softmax → SpMM, forward and
backward, all heads of a launch at once (DESIGN.md §8–§9).

The chain (graph attention / sparse transformer): for a sparsity pattern
(rows, cols) over queries Q (n_rows, H, d), keys K (n_cols, H, d) and
values V (n_cols, H, dv), per head h and entry t,

    pre[t] = <Q[r_t], K[c_t]> · scale (+ bias[t])     score 'dot'
             Q[r_t] + K[c_t] (+ bias[t])             score 'additive'
    e[t]   = pre[t]                                   'dot'
             LeakyReLU_slope(pre[t])                  'additive' (GAT)
    w[t]   = softmax of e over {t' : r_t' = r_t}
    out[r] = Σ_{t: r_t = r} w[t] · keep[t] · V[c_t]

``keep`` is an optional (nnz, H) mask on the normalised coefficients
(dropout: 0 or 1/(1-p)); the additive form's Q and K are GAT's per-node,
per-head terms ``a_l·Wh`` and ``a_r·Wh`` (d = 1).

**Layout.**  Heads lie in lanes: every per-node operand is a 2-D
node-major table, (rows, H·width), so one dynamic row window of a table
brings all heads of a node at once and one lane of the launch serves
every head.  The tables stay resident in VMEM for the whole launch,
their heights padded to a sublane tile.  The pattern's row and column
ids travel as (1, T) lane blocks in SMEM, read one scalar a lane; the
row ids once more as a VMEM lane block for the window products.

**Forward.**  The softmax is the same for any per-row shift m that keeps
exp(e - m) from overflowing.  Given a shift table (grid (nnz_tiles,)),
the launch is one pass: each lane gathers Q[r], K[c], V[c] and m[r] a
lane at a time into (T, ·) scratch rows, forms p = exp(e - m) and
scatter-adds ``[p·keep ⊗ V | p]`` by row into one accumulator: the
weighted values and the denominator l in one reduction ('segment': MXU
window products, ``common.window_reduce_scatter``).  The additive score
takes its shift from bounds (:func:`additive_shift`, in XLA): LeakyReLU
is increasing, so row i's largest score lies between LeakyReLU(q_i +
min k + min bias) and LeakyReLU(q_i + max k + max bias); the shift sits
at most :data:`SHIFT_ROOM` (60) below the upper bound and above the
lower, so every term is at most e^60 and the row's largest at least
e^-60.  Where a row's bounds lie more than 120 apart, or are not finite,
the exact row maximum is taken instead.  Without a shift
(the dot score) the grid is (2, nnz_tiles): phase 0 gathers Q[r] and
K[c], computes the scores and scatters their row maximum (the max
monoid, the registry's 'accumulate' realization: one read-modify-write
a lane), and phase 1 is the pass above with the finished max.  The
caller divides by l.  Either way the forward returns the (m, l) it
used, so the backward's w = exp(e - m)/l is the forward's weight.

**Backward** (grid (nnz_tiles,)): one pass.  The softmax backward's row
dot is δ[r] = Σ_t w·dw = <dout[r], out[r]> (the forward's output), which
the caller computes, so each lane recomputes w from the forward's (m, l)
and has everything it needs: dV[c] += w·keep·dout[r],
de = w·(keep·<dout[r], V[c]> − δ[r]), then dQ by row (window products)
and dK by column (with dV, one 'accumulate' read-modify-write a lane:
columns are in no order a window could use).

Scores, statistics and probabilities are float32 whatever the storage
dtype (``common.upcast_f32``).  Padded lanes (the tile round-up) are
masked by the static true ``nnz``; empty rows come out as exact zeros.
A head-sum or head-broadcast of per-lane values is an MXU product with a
0/1 matrix at ``HIGHEST``, exact for a broadcast.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (
    NEG_INF,
    block_buffers,
    group_reduce_scatter,
    pallas_call,
    segment_window,
    upcast_f32,
    vmem_bytes,
    window_reduce_scatter,
)

__all__ = [
    "NEG_INF",
    "SCORES",
    "SHIFT_ROOM",
    "additive_shift",
    "fused_sparse_attention",
    "fused_sparse_attention_bwd",
    "sparse_attention_bwd_ref",
    "sparse_attention_ref",
    "sparse_softmax_weights",
]

#: the score forms: a scaled dot product, or GAT's LeakyReLU of a sum
SCORES = ("dot", "additive")
HIGHEST = jax.lax.Precision.HIGHEST


def _sublanes(n: int) -> int:
    """``n`` rounded up to a sublane tile (8 rows)."""
    return -(-n // 8) * 8


# ---------------------------------------------------------------------------
# Pure-JAX spec oracles (one head: q (n, d), k/v (n_cols, ·), keep (nnz,))
# ---------------------------------------------------------------------------


def _pre_scores(rows, cols, q, k, *, scale, bias, score):
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    if score == "dot":
        pre = jnp.sum(qf[rows] * kf[cols], axis=-1) * scale
    elif score == "additive":
        pre = qf[rows, 0] + kf[cols, 0]
    else:
        raise ValueError(f"score must be one of {SCORES}, not {score!r}")
    if bias is not None:
        pre = pre + bias.astype(jnp.float32)
    return pre


def _activate(pre, score, slope):
    return pre if score == "dot" else jnp.where(pre > 0, pre, slope * pre)


def sparse_softmax_weights(rows, cols, q, k, *, n_rows: int, scale: float,
                           bias=None, score: str = "dot", slope: float = 0.2):
    """Spec of the score → segment-softmax front half: the normalized
    per-nnz attention weights ``w`` (before any keep mask).  Shared by
    the forward oracle and the spec VJP, so the empty-row isfinite guard
    and the 1e-30 denominator floor cannot desynchronize.  ``bias`` is an
    optional (nnz,) additive score term (a CSR adjacency's stored
    values)."""
    s = _activate(_pre_scores(rows, cols, q, k, scale=scale, bias=bias,
                              score=score), score, slope)
    m = jax.ops.segment_max(s, rows, num_segments=n_rows)
    m = jnp.where(jnp.isfinite(m), m, 0.0)  # empty rows: any finite value
    p = jnp.exp(s - m[rows])
    l = jax.ops.segment_sum(p, rows, num_segments=n_rows)
    return p / jnp.maximum(l[rows], 1e-30)


def sparse_attention_ref(rows, cols, q, k, v, *, n_rows: int,
                         scale: float | None = None, bias=None,
                         score: str = "dot", slope: float = 0.2, keep=None):
    """Executable specification of the fused kernel for one head (the
    oracle the kernel and its VJP are tested against).  Empty rows ->
    zero rows."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    w = sparse_softmax_weights(rows, cols, q, k, n_rows=n_rows, scale=scale,
                               bias=bias, score=score, slope=slope)
    if keep is not None:
        w = w * keep.astype(jnp.float32)
    return jax.ops.segment_sum(w[:, None] * v.astype(jnp.float32)[cols],
                               rows, num_segments=n_rows)


def sparse_attention_bwd_ref(rows, cols, q, k, v, dout, *, n_rows: int,
                             scale: float, bias=None, score: str = "dot",
                             slope: float = 0.2, keep=None):
    """Spec-recompute VJP of one head: the softmax backward and the
    sampled / transpose products through segment ops, recomputing the
    weights from scratch.  Returns ``(dq, dk, dv)``."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    do = dout.astype(jnp.float32)
    kp = 1.0 if keep is None else keep.astype(jnp.float32)
    pre = _pre_scores(rows, cols, q, k, scale=scale, bias=bias, score=score)
    w = sparse_softmax_weights(rows, cols, q, k, n_rows=n_rows, scale=scale,
                               bias=bias, score=score, slope=slope)
    dv_ = jax.ops.segment_sum((w * kp)[:, None] * do[rows], cols,
                              num_segments=v.shape[0])
    dw = jnp.sum(do[rows] * vf[cols], axis=-1) * kp
    delta = jax.ops.segment_sum(w * dw, rows, num_segments=n_rows)
    de = w * (dw - delta[rows])
    if score == "dot":
        dpre = de * scale
        dq = jax.ops.segment_sum(dpre[:, None] * kf[cols], rows,
                                 num_segments=n_rows)
        dk = jax.ops.segment_sum(dpre[:, None] * qf[rows], cols,
                                 num_segments=k.shape[0])
    else:
        dpre = de * jnp.where(pre > 0, 1.0, slope)
        dq = jax.ops.segment_sum(dpre, rows, num_segments=n_rows)[:, None]
        dk = jax.ops.segment_sum(dpre, cols, num_segments=k.shape[0])[:, None]
    return dq, dk, dv_


# ---------------------------------------------------------------------------
# In-kernel pieces
# ---------------------------------------------------------------------------


def _heads_matrix(n_heads: int, width: int):
    """(H, H·width) 0/1: head h's lanes of a head-major row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (n_heads, n_heads * width), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (n_heads, n_heads * width), 0)
    return ((lane >= head * width) & (lane < head * width + width)).astype(
        jnp.float32)


def _spread(x, width: int):
    """(T, H) per-head values -> (T, H·width), each repeated over its
    head's lanes."""
    if width == 1:
        return x
    return jnp.dot(x, _heads_matrix(x.shape[1], width), precision=HIGHEST,
                   preferred_element_type=jnp.float32)


def _head_sum(x, width: int, n_heads: int):
    """(T, H·width) -> (T, H): the sum of each head's lanes."""
    if width == 1:
        return x
    return jax.lax.dot_general(
        x, _heads_matrix(n_heads, width), (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _scores(qg, kg, bias, *, score, d, n_heads, scale, slope):
    """``(pre, e)`` of a tile's lanes, each (T, H)."""
    if score == "dot":
        pre = _head_sum(qg * kg, d, n_heads) * scale
    else:
        pre = qg + kg
    if bias is not None:
        pre = pre + bias
    return pre, _activate(pre, score, slope)


def _lane_mask(nnz: int, tile: int, i):
    """(T, 1): which lanes of nnz tile ``i`` are entries, not padding."""
    lane = i * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    return lane < nnz


def _gather(pairs, t):
    """Lane ``t``: copy the row ``idx`` of each ``(table, idx, dst)`` into
    row ``t`` of ``dst`` (a dynamic one-row window a table)."""
    for table, idx, dst in pairs:
        dst[pl.ds(t, 1), :] = table[pl.ds(idx, 1), :]


def _row_add(rows_ref, lanes_ref, part_ref, out_ref, group_size, strategy):
    """Add a tile's partials into ``out_ref`` by row: MXU window products
    where the 'segment' window engages, else the strategy's walk."""
    T = part_ref.shape[0]
    if segment_window(strategy, out_ref.shape[0], T) is not None:
        window_reduce_scatter(rows_ref, lanes_ref, part_ref, out_ref,
                              group_size, strategy)
    else:
        group_reduce_scatter(rows_ref, part_ref, out_ref, group_size, strategy)


def _lane_specs(nnz_tile: int, n_heads: int, has_bias: bool, has_keep: bool,
                index):
    """Specs of the lane operands: row and column ids in SMEM, the row ids
    again in VMEM, the bias lanes and the (H, T) keep block."""
    def spec(rows, space=None):
        return pl.BlockSpec((None, rows, nnz_tile), index, memory_space=space)

    specs = [spec(1, pltpu.SMEM), spec(1, pltpu.SMEM), spec(1)]
    if has_bias:
        specs.append(spec(1))
    if has_keep:
        specs.append(spec(n_heads))
    return specs


def _lane_operands(rows, cols, bias, keep, nnz_tile: int):
    tiles = rows.shape[0] // nnz_tile
    lanes = lambda x: x.reshape(tiles, 1, nnz_tile)  # noqa: E731
    ops = [lanes(rows), lanes(cols), lanes(rows)]
    if bias is not None:
        ops.append(lanes(bias.astype(jnp.float32)))
    if keep is not None:
        # (nnz_pad, H) -> (tiles, H, T): a lane-dense block a tile
        ops.append(keep.astype(jnp.float32).reshape(tiles, nnz_tile, -1)
                   .transpose(0, 2, 1))
    return ops


def _unpack_lanes(refs, has_bias: bool, has_keep: bool):
    rows_ref, cols_ref, lanes_ref, *refs = refs
    bias_ref = refs.pop(0) if has_bias else None
    keep_ref = refs.pop(0) if has_keep else None
    return rows_ref, cols_ref, lanes_ref, bias_ref, keep_ref, refs


def _lane_values(bias_ref, keep_ref):
    """The tile's bias as a (T, 1) column and keep mask as (T, H)."""
    bias = None if bias_ref is None else bias_ref[...].T
    keep = None if keep_ref is None else keep_ref[...].T
    return bias, keep


def _table(x, n_pad: int):
    """(n, H, w) or (n, H·w) -> the (n_pad, H·w) float32 node-major
    table."""
    n = x.shape[0]
    x = x.reshape(n, -1).astype(jnp.float32)
    return jnp.pad(x, ((0, n_pad - n), (0, 0))) if n_pad != n else x


def _check(rows, q, k, v, nnz_tile, score, strategy):
    assert rows.shape[0] % nnz_tile == 0, (rows.shape, nnz_tile)
    assert q.ndim == k.ndim == v.ndim == 3, (q.shape, k.shape, v.shape)
    assert q.shape[1] == k.shape[1] == v.shape[1], (q.shape, k.shape, v.shape)
    assert k.shape[0] == v.shape[0] and q.shape[2] == k.shape[2]
    if score not in SCORES:
        raise ValueError(f"score must be one of {SCORES}, not {score!r}")
    if score == "additive" and q.shape[2] != 1:
        raise ValueError("the additive score takes per-node terms of width 1")
    if strategy == "parallel":
        raise ValueError("the attention kernels cannot run 'parallel': "
                         "its one-writeback contract does not hold for rows")


# ---------------------------------------------------------------------------
# The additive score's softmax shift
# ---------------------------------------------------------------------------

#: how far the shift may sit from its row's largest score, on either side.
#: LeakyReLU is increasing, so a row i that holds an entry has its largest
#: score between lo_i = LeakyReLU(q_i + min k + min bias) and
#: hi_i = LeakyReLU(q_i + max k + max bias).  The shift min(hi_i,
#: lo_i + 60) then keeps every term exp(e - shift) at most e^(gap - 60)
#: and the row's largest at least e^-60, where gap = hi_i - lo_i.  So
#: while every row's gap is at most 2 · 60, no term passes e^60 ≈ 1.1e26
#: (a row of d terms and values v sums to at most 1.1e26·d·|v|, far from
#: float32's 3.4e38), the largest is at least e^-60 ≈ 8.8e-27, a normal
#: float32, and a term is lost only where its weight relative to the
#: row's largest is below e^-27 ≈ 1.9e-12, far under float32's rounding
#: of the sum.  A gap of at most 60 leaves the shift at hi_i, at or above
#: every score of its row.
SHIFT_ROOM = 60.0


def additive_shift(rows, cols, q, k, bias=None, *, n_rows: int,
                   slope: float):
    """The (n_rows, H) float32 softmax shift of the additive score:
    ``min(hi, lo + SHIFT_ROOM)`` between the bounds ``hi =
    LeakyReLU(q + max k + max bias)`` and ``lo = LeakyReLU(q + min k +
    min bias)`` on each row's largest score, where every row's gap
    ``hi - lo`` is at most 2 · :data:`SHIFT_ROOM`; else (a NaN or inf gap
    too) the exact row maximum, with empty rows 0.  rows/cols and bias
    (nnz,) are the true entries, q (n_rows, H, 1), k (n_kv, H, 1).  XLA
    alone: the bounds are two reductions over k and elementwise ops on
    q; the exact branch runs only where the predicate asks for it."""
    qf = q.reshape(q.shape[0], -1).astype(jnp.float32)
    kf = k.reshape(k.shape[0], -1).astype(jnp.float32)
    # a bias of no entries adds to no score
    bf = None if bias is None or bias.shape[0] == 0 else bias.astype(jnp.float32)
    with jax.named_scope("attn.shift"):
        k_max, k_min = jnp.max(kf, axis=0), jnp.min(kf, axis=0)
        if bf is not None:
            k_max, k_min = k_max + jnp.max(bf), k_min + jnp.min(bf)
        hi = lambda: _activate(qf + k_max, "additive", slope)  # noqa: E731
        lo = lambda: _activate(qf + k_min, "additive", slope)  # noqa: E731
        fits = jnp.all(hi() - lo() <= 2 * SHIFT_ROOM)

        def bound():
            with jax.named_scope("bound"):
                return jnp.minimum(hi(), lo() + SHIFT_ROOM)

        def exact():
            with jax.named_scope("exact"):
                pre = qf[rows] + kf[cols]
                if bf is not None:
                    pre = pre + bf[:, None]
                m = jax.ops.segment_max(_activate(pre, "additive", slope),
                                        rows, num_segments=n_rows)
                return jnp.where(jnp.isfinite(m), m, 0.0)

        return jax.lax.cond(fits, bound, exact)


# ---------------------------------------------------------------------------
# The forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, nnz: int, d: int, dv: int, scale: float, score: str,
                slope: float, group_size: int, strategy: str, has_bias: bool,
                has_keep: bool, shifted: bool):
    rows_ref, cols_ref, lanes_ref, bias_ref, keep_ref, refs = _unpack_lanes(
        refs, has_bias, has_keep)
    if shifted:  # the shift m is an input: one pass over the nnz tiles
        q_ref, k_ref, v_ref, m_ref, acc_ref, *scratch = refs
        i = pl.program_id(0)
    else:
        q_ref, k_ref, v_ref, acc_ref, m_ref, *scratch = refs
        ph, i = pl.program_id(0), pl.program_id(1)
    qg_ref, kg_ref, vg_ref, mg_ref, part_ref = scratch
    T, H = mg_ref.shape
    valid = _lane_mask(nnz, T, i)
    bias, keep = _lane_values(bias_ref, keep_ref)

    def scores():
        return _scores(upcast_f32(qg_ref[...]), upcast_f32(kg_ref[...]), bias,
                       score=score, d=d, n_heads=H, scale=scale,
                       slope=slope)[1]

    def init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def weighted_sum():
        def lane(t, c):
            r, col = rows_ref[0, t], cols_ref[0, t]
            _gather(((q_ref, r, qg_ref), (k_ref, col, kg_ref),
                     (v_ref, col, vg_ref), (m_ref, r, mg_ref)), t)
            return c

        jax.lax.fori_loop(0, T, lane, 0)
        p = jnp.where(valid, jnp.exp(scores() - mg_ref[...]), 0.0)
        pk = p if keep is None else p * keep
        part_ref[:, :H * dv] = _spread(pk, dv) * upcast_f32(vg_ref[...])
        part_ref[:, H * dv:] = p
        _row_add(rows_ref, lanes_ref, part_ref, acc_ref, group_size, strategy)

    if shifted:
        pl.when(i == 0)(init_acc)
        weighted_sum()
        return

    @pl.when((ph == 0) & (i == 0))
    def _init_max():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    pl.when((ph == 1) & (i == 0))(init_acc)

    @pl.when(ph == 0)
    def _row_max():
        def lane(t, c):
            _gather(((q_ref, rows_ref[0, t], qg_ref),
                     (k_ref, cols_ref[0, t], kg_ref)), t)
            return c

        jax.lax.fori_loop(0, T, lane, 0)
        mg_ref[...] = jnp.where(valid, scores(), NEG_INF)
        group_reduce_scatter(rows_ref, mg_ref, m_ref, group_size,
                             "accumulate", op="max")

    pl.when(ph == 1)(weighted_sum)


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "nnz", "nnz_tile", "scale", "group_size",
                     "strategy", "score", "slope", "interpret"),
)
def fused_sparse_attention(rows, cols, q, k, v, *, n_rows: int, nnz: int,
                           nnz_tile: int = 256, scale: float = 1.0,
                           group_size: int = 32, strategy: str = "segment",
                           bias=None, score: str = "dot", slope: float = 0.2,
                           keep=None, shift=None,
                           interpret: bool | None = None):
    """One launch of the fused forward over all heads.

    rows/cols (and bias) are (nnz_pad,) with nnz_pad % nnz_tile == 0
    (``nnz`` is the true count; pad lanes are masked); q (n_rows, H, d),
    k (n_kv, H, d), v (n_kv, H, dv) node-major, d = 1 for the additive
    score; ``keep`` an optional (nnz_pad, H) mask on the normalised
    coefficients; ``shift`` an optional (n_rows, H) per-row softmax shift
    at or above every score of its row (:func:`additive_shift`): given,
    the launch is one pass, without the row-max phase.  Returns
    ``(out, m, l)``: out (n_rows, H, dv), and the shift m (the row max,
    or ``shift``) and denominator l (n_rows, H), the O(H·n_rows)
    residuals the backward recomputes the weights from.  ``interpret``
    defaults to the backend's answer (``common.pallas_call``)."""
    _check(rows, q, k, v, nnz_tile, score, strategy)
    n_kv, H, d = k.shape
    dv = v.shape[2]
    R, C = _sublanes(n_rows), _sublanes(n_kv)
    shifted = shift is not None
    tables = [_table(q, R), _table(k, C), _table(v, C)]
    if shifted:
        tables.append(_table(shift, R))
    tiles = rows.shape[0] // nnz_tile
    grid = (tiles,) if shifted else (2, tiles)
    resident = lambda *_: (0, 0)  # noqa: E731
    lanes = _lane_operands(rows, cols, bias, keep, nnz_tile)
    in_specs = _lane_specs(nnz_tile, H, bias is not None, keep is not None,
                           lambda *g: (g[-1], 0, 0))
    in_specs += [pl.BlockSpec(t.shape, resident) for t in tables]
    out_shape = [jax.ShapeDtypeStruct((R, H * dv + H), jnp.float32)]
    if not shifted:
        out_shape.append(jax.ShapeDtypeStruct((R, H), jnp.float32))
    scratch = [(nnz_tile, H * d), (nnz_tile, H * d), (nnz_tile, H * dv),
               (nnz_tile, H), (nnz_tile, H * dv + H)]
    kernel = functools.partial(
        _fwd_kernel, nnz=nnz, d=d, dv=dv, scale=scale, score=score,
        slope=slope, group_size=group_size, strategy=strategy,
        has_bias=bias is not None, has_keep=keep is not None, shifted=shifted)
    acc, *m = pallas_call(
        kernel,
        name="fused_attention_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(s.shape, resident) for s in out_shape],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        vmem_need=_vmem_need(lanes, tables, out_shape, scratch),
        interpret=interpret,
    )(*lanes, *tables)
    m = tables[3] if shifted else m[0]
    l = acc[:n_rows, H * dv:]
    out = acc[:n_rows, :H * dv].reshape(n_rows, H, dv)
    return out / jnp.maximum(l, 1e-30)[..., None], m[:n_rows], l


def _vmem_need(lanes, tables, out_shape, scratch) -> int:
    """Padded, buffered VMEM bytes of a launch: its lane blocks
    double-buffered, its resident tables and outputs once, its scratch."""
    need = sum(vmem_bytes(x.shape[1:], x.dtype, block_buffers(2))
               for x in lanes)
    need += sum(vmem_bytes(x.shape, x.dtype) for x in (*tables, *out_shape))
    return need + sum(vmem_bytes(s, jnp.float32) for s in scratch)


# ---------------------------------------------------------------------------
# The backward kernel
# ---------------------------------------------------------------------------


def _bwd_kernel(*refs, nnz: int, d: int, dv: int, scale: float, score: str,
                slope: float, group_size: int, strategy: str, has_bias: bool,
                has_keep: bool):
    rows_ref, cols_ref, lanes_ref, bias_ref, keep_ref, refs = _unpack_lanes(
        refs, has_bias, has_keep)
    (q_ref, k_ref, v_ref, do_ref, st_ref, dq_ref, dkv_ref,
     qg_ref, kg_ref, vg_ref, dog_ref, sg_ref, pq_ref, pkv_ref) = refs
    i = pl.program_id(0)
    T = qg_ref.shape[0]
    H = sg_ref.shape[1] // 3
    valid = _lane_mask(nnz, T, i)
    bias, keep = _lane_values(bias_ref, keep_ref)

    @pl.when(i == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)
        dkv_ref[...] = jnp.zeros_like(dkv_ref)

    def lane(t, c):
        r, col = rows_ref[0, t], cols_ref[0, t]
        _gather(((q_ref, r, qg_ref), (k_ref, col, kg_ref), (v_ref, col, vg_ref),
                 (do_ref, r, dog_ref), (st_ref, r, sg_ref)), t)
        return c

    jax.lax.fori_loop(0, T, lane, 0)
    qg, kg = upcast_f32(qg_ref[...], kg_ref[...])
    vg, dog = upcast_f32(vg_ref[...], dog_ref[...])
    st = sg_ref[...]
    m, linv, delta = st[:, :H], st[:, H:2 * H], st[:, 2 * H:]
    pre, e = _scores(qg, kg, bias, score=score, d=d, n_heads=H, scale=scale,
                     slope=slope)
    w = jnp.where(valid, jnp.exp(e - m) * linv, 0.0)
    wk = w if keep is None else w * keep
    dw = _head_sum(dog * vg, dv, H)
    if keep is not None:
        dw = dw * keep
    de = w * (dw - delta)
    if score == "dot":
        ds = _spread(de * scale, d)
        gq, gk = ds * kg, ds * qg
    else:
        gq = gk = de * jnp.where(pre > 0, 1.0, slope)
    pq_ref[...] = gq
    pkv_ref[:, :H * d] = gk
    pkv_ref[:, H * d:] = _spread(wk, dv) * dog
    _row_add(rows_ref, lanes_ref, pq_ref, dq_ref, group_size, strategy)
    group_reduce_scatter(cols_ref, pkv_ref, dkv_ref, group_size, "accumulate")


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "nnz", "nnz_tile", "scale", "group_size",
                     "strategy", "score", "slope", "interpret"),
)
def fused_sparse_attention_bwd(rows, cols, q, k, v, out, dout, m, l, *,
                               n_rows: int, nnz: int, nnz_tile: int = 256,
                               scale: float = 1.0, group_size: int = 32,
                               strategy: str = "segment", bias=None,
                               score: str = "dot", slope: float = 0.2,
                               keep=None, interpret: bool | None = None):
    """One launch of the fused backward: ``(dq, dk, dv)`` for all heads,
    in the layouts of :func:`fused_sparse_attention`; ``out``, ``m`` and
    ``l`` are what the forward returned, ``dout`` (n_rows, H, dv) the
    output's cotangent."""
    _check(rows, q, k, v, nnz_tile, score, strategy)
    n_kv, H, d = k.shape
    dv = v.shape[2]
    R, C = _sublanes(n_rows), _sublanes(n_kv)
    delta = jnp.sum(dout.astype(jnp.float32) * out, axis=-1)
    stats = jnp.concatenate([m, 1.0 / jnp.maximum(l, 1e-30), delta], axis=1)
    tables = [_table(q, R), _table(k, C), _table(v, C), _table(dout, R),
              _table(stats, R)]
    lanes = _lane_operands(rows, cols, bias, keep, nnz_tile)
    resident = lambda i: (0, 0)  # noqa: E731
    in_specs = _lane_specs(nnz_tile, H, bias is not None, keep is not None,
                           lambda i: (i, 0, 0))
    in_specs += [pl.BlockSpec(t.shape, resident) for t in tables]
    out_shape = [jax.ShapeDtypeStruct((R, H * d), jnp.float32),
                 jax.ShapeDtypeStruct((C, H * d + H * dv), jnp.float32)]
    scratch = [(nnz_tile, H * d), (nnz_tile, H * d), (nnz_tile, H * dv),
               (nnz_tile, H * dv), (nnz_tile, 3 * H), (nnz_tile, H * d),
               (nnz_tile, H * d + H * dv)]
    kernel = functools.partial(
        _bwd_kernel, nnz=nnz, d=d, dv=dv, scale=scale, score=score,
        slope=slope, group_size=group_size, strategy=strategy,
        has_bias=bias is not None, has_keep=keep is not None)
    dq, dkv = pallas_call(
        kernel,
        name="fused_attention_bwd",
        grid=(rows.shape[0] // nnz_tile,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(s.shape, resident) for s in out_shape],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch],
        vmem_need=_vmem_need(lanes, tables, out_shape, scratch),
        interpret=interpret,
    )(*lanes, *tables)
    return (dq[:n_rows].reshape(n_rows, H, d),
            dkv[:n_kv, :H * d].reshape(n_kv, H, d),
            dkv[:n_kv, H * d:].reshape(n_kv, H, dv))
