"""Distributed sparse ops via shard_map — the paper's reduction-strategy
choice *elevated to the collective level* (DESIGN.md §12).

Three partitionings of ``out = A @ B`` (and of sparse attention):

row         A row-partitioned over the axis; no collectives (each shard
            owns whole output rows) — the collective analogue of parallel
            reduction / one writeback thread.
nnz_ar      A nnz-partitioned; each shard computes a full-height partial
            and an **all-reduce** combines — the analogue of atomicAdd
            (every shard "writes" every row).
nnz_rs      A nnz-partitioned; partials combined with **reduce-scatter**
            so each shard finalizes its own row block — the analogue of
            segment reduction (multiple writeback shards, targets decided
            by data layout). Moves 1/P the bytes of nnz_ar on the wire per
            shard output.

All three compute identical results; they differ in collective bytes and
balance, which is exactly the axis the paper tunes.  The mode is carried
by ``Schedule.collective`` so the distributed tuner
(:func:`repro.tune.tune_dist_spmm`) picks kernel tiling and wire strategy
in one pass; ``repro.roofline.analysis.predict_collective_bytes``
predicts the wire traffic each mode compiles to.

Shard-local compute runs the *tuned Pallas kernels* (``kernels.ops.spmm``
over a shard-local :class:`GroupedCOO`, ``fused_sparse_attention`` for
attention) — not the pure-jnp reference — so the distributed path keeps
the schedule work of DESIGN.md §6–§11.

Padding contract: attention has no values to zero-extend with, so the
partition helpers route pad lanes to a **phantom row** appended after the
real rows; each shard computes it like any other row and the wrappers
crop it before (row mode) or alongside (nnz modes) the collective.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core import COLLECTIVES, Schedule
from ..kernels import ops as kops
from ..kernels.fused_attention import NEG_INF, fused_sparse_attention
from .formats import GroupedCOO, round_up

#: ``jax.shard_map`` with replication checking off: pallas_call has no
#: replication rule.
shard_map = functools.partial(jax.shard_map, check_vma=False)

__all__ = [
    "COLLECTIVES",
    "dist_attention_shard_map",
    "dist_spmm",
    "partition_nnz_coo",
    "partition_rows_coo",
    "shard_nnz_counts",
    "spmm_shard_map",
]


# ---------------------------------------------------------------------------
# Host-side partition helpers (concrete arrays in, shardable arrays out)
# ---------------------------------------------------------------------------


def _np_triplet(csr, pattern_only: bool):
    coo = csr.tocoo()
    rows = np.asarray(coo.rows, np.int32)
    cols = np.asarray(coo.cols, np.int32)
    vals = None if pattern_only else np.asarray(coo.vals)
    return rows, cols, vals


def partition_nnz_coo(csr, axis_size: int, nnz_tile: int = 256, *,
                      pattern_only: bool = False, phantom_row: bool = False):
    """Row-sorted COO triplets padded so every shard of an
    ``axis_size``-way nnz split gets an equal, ``nnz_tile``-aligned slice.

    ``pattern_only`` drops the value stream (attention patterns);
    ``phantom_row`` targets pad lanes at row ``n_rows`` (one past the
    end) instead of zero-extending into row ``n_rows - 1`` — required
    whenever pad lanes have no zero value to neutralize them (attention).
    Returns ``(rows, cols, vals_or_None, nnz)``.
    """
    rows, cols, vals = _np_triplet(csr, pattern_only)
    nnz = int(rows.shape[0])
    per = round_up(max(nnz, 1), nnz_tile * axis_size)
    pad = per - nnz
    pad_row = csr.shape[0] if phantom_row else csr.shape[0] - 1
    rows = np.concatenate([rows, np.full((pad,), pad_row, np.int32)])
    cols = np.concatenate([cols, np.zeros((pad,), np.int32)])
    if vals is not None:
        vals = np.concatenate([vals, np.zeros((pad,), vals.dtype)])
    return (jnp.asarray(rows), jnp.asarray(cols),
            None if vals is None else jnp.asarray(vals), nnz)


def partition_rows_coo(csr, axis_size: int, nnz_tile: int = 256, *,
                       pattern_only: bool = False, phantom_row: bool = False):
    """Bucket the triplets by contiguous row blocks of ``n_rows /
    axis_size`` and pad every bucket to one common ``nnz_tile``-aligned
    length, re-indexing rows to bucket-local ids.

    The concatenation shards evenly over the mesh axis, giving each shard
    the triplets of exactly its own output rows (the 'row' / parallel
    collective).  Pad lanes target the bucket's last local row
    (``phantom_row=False``, zero-extension) or the local phantom row
    ``n_rows_local`` (``phantom_row=True``).  Returns ``(rows, cols,
    vals_or_None, shard_nnz)`` with ``shard_nnz`` the per-bucket true
    lane counts (the balance statistic the tuner seeds from).
    """
    n_rows = csr.shape[0]
    if n_rows % axis_size:
        raise ValueError(
            f"row partitioning needs n_rows ({n_rows}) divisible by the "
            f"axis size ({axis_size})")
    rows, cols, vals = _np_triplet(csr, pattern_only)
    block = n_rows // axis_size
    bucket = rows // block
    counts = np.bincount(bucket, minlength=axis_size)
    per = round_up(max(int(counts.max()), 1), nnz_tile)
    pad_row = block if phantom_row else block - 1
    out_r = np.full((axis_size, per), pad_row, np.int32)
    out_c = np.zeros((axis_size, per), np.int32)
    out_v = (None if vals is None
             else np.zeros((axis_size, per), vals.dtype))
    for s in range(axis_size):
        sel = bucket == s
        k = int(counts[s])
        out_r[s, :k] = rows[sel] - s * block
        out_c[s, :k] = cols[sel]
        if out_v is not None:
            out_v[s, :k] = vals[sel]
    return (jnp.asarray(out_r.reshape(-1)), jnp.asarray(out_c.reshape(-1)),
            None if out_v is None else jnp.asarray(out_v.reshape(-1)),
            [int(c) for c in counts])


def shard_nnz_counts(csr, axis_size: int, collective: str):
    """Per-shard true-nnz counts under ``collective``'s partitioning —
    the balance statistic ``tune_dist_spmm`` seeds candidates from.
    nnz splits are balanced by construction; row splits inherit the
    matrix's row-block skew."""
    if collective == "row":
        n_rows = csr.shape[0]
        if n_rows % axis_size:
            return None  # row mode infeasible on this mesh
        block = n_rows // axis_size
        lengths = np.asarray(csr.row_lengths())
        return [int(lengths[s * block:(s + 1) * block].sum())
                for s in range(axis_size)]
    base, extra = divmod(int(csr.nnz), axis_size)
    return [base + (1 if s < extra else 0) for s in range(axis_size)]


# ---------------------------------------------------------------------------
# Distributed SpMM
# ---------------------------------------------------------------------------


def _local_spmm(rows, cols, vals, b, n_rows, schedule: Schedule):
    """Shard-local tuned Pallas SpMM over a (traced) padded COO slice.

    The skew layout is a host-side pass over concrete indices, and the
    rb kernel needs an ELL conversion — neither is traceable inside
    shard_map, so skew thresholds are stripped and rb schedules fall
    back to the eb kernel at the same column tile.
    """
    s = schedule
    if s.is_skew:
        s = s.replace(split_threshold=None, merge_threshold=None)
    if s.kernel != "eb":
        s = Schedule("eb", col_tile=s.col_tile)
    nnz_local = int(rows.shape[0])
    pad = round_up(max(nnz_local, 1), s.nnz_tile) - nnz_local
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.full((pad,), n_rows - 1, jnp.int32)])
        cols = jnp.concatenate([cols, jnp.zeros((pad,), jnp.int32)])
        vals = jnp.concatenate([vals, jnp.zeros((pad,), vals.dtype)])
    g = GroupedCOO(rows=rows, cols=cols, vals=vals,
                   shape=(n_rows, int(b.shape[0])),
                   nnz=nnz_local, nnz_tile=s.nnz_tile)
    return kops.spmm(g, b, s)


def _resolve_collective(mode, schedule):
    if schedule is not None and schedule.collective is not None:
        if mode is not None and mode != schedule.collective:
            raise ValueError(
                f"mode={mode!r} conflicts with schedule.collective="
                f"{schedule.collective!r}; pass one or the other")
        return schedule.collective
    if mode is None:
        return "nnz_rs"
    if mode not in COLLECTIVES:
        raise ValueError(f"unknown mode {mode!r}; known: {COLLECTIVES}")
    return mode


def spmm_shard_map(rows, cols, vals, b, *, n_rows: int, mesh, axis: str,
                   mode: str | None = None,
                   schedule: Schedule | None = None):
    """rows/cols/vals: (nnz_pad,) padded COO (pad val=0); b: (K, N).

    Sharding contract (enforced via shard_map in/out specs):
      row:     triplets already row-partitioned; rows are *local* indices
               (:func:`partition_rows_coo` builds this layout).
      nnz_*:   triplets nnz-partitioned (any rows anywhere); rows global.
    Returns out (n_rows, N) sharded over ``axis`` on rows (row/nnz_rs) or
    replicated (nnz_ar).

    ``schedule`` drives the shard-local Pallas kernel (tiling, group
    size, strategy) and — via ``schedule.collective`` — the wire mode;
    the legacy ``mode=`` keyword still selects the mode when the
    schedule leaves it unset.  Defaults: library schedule, 'nnz_rs'.
    """
    sched = Schedule() if schedule is None else schedule
    mode = _resolve_collective(mode, schedule)
    axis_size = mesh.shape[axis]
    if mode == "row":
        if n_rows % axis_size:
            raise ValueError(
                f"row mode needs n_rows ({n_rows}) divisible by the axis "
                f"size ({axis_size})")

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P()),
            out_specs=P(axis),
        )
        def _row(r, c, v, bb):
            return _local_spmm(r, c, v, bb, n_rows // axis_size, sched)

        return _row(rows, cols, vals, b)

    if mode == "nnz_ar":

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P()),
            out_specs=P(),
        )
        def _ar(r, c, v, bb):
            partial = _local_spmm(r, c, v, bb, n_rows, sched)
            return jax.lax.psum(partial, axis)  # atomic-style combine

        return _ar(rows, cols, vals, b)

    # nnz_rs
    if n_rows % axis_size:
        raise ValueError(
            f"nnz_rs mode needs n_rows ({n_rows}) divisible by the axis "
            f"size ({axis_size})")

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=P(axis),
    )
    def _rs(r, c, v, bb):
        partial = _local_spmm(r, c, v, bb, n_rows, sched)
        # segment-style combine: each shard finalizes its row block
        return jax.lax.psum_scatter(
            partial, axis, scatter_dimension=0, tiled=True)

    return _rs(rows, cols, vals, b)


def dist_spmm(csr, b, *, mesh, axis: str, schedule=None,
              cache=None, backend=None):
    """``csr @ b`` under shard_map, partitioning chosen by the schedule.

    ``schedule`` accepts a :class:`Schedule` (its ``collective`` picks
    the partitioning, default 'nnz_rs'), or ``"tune"`` — run/replay the
    distributed tuner (:func:`repro.tune.tune_dist_spmm`, per-backend
    cache namespace) so one call picks kernel tiling, wire mode *and*
    value storage dtype in a single joint search.  A narrow tuned
    ``value_dtype`` narrows the sharded value feed (and the dense
    operand) host-side, so deployment moves the bytes the tuner timed.
    """
    if schedule == "tune":
        from ..tune import tune_dist_spmm

        schedule = tune_dist_spmm(csr, int(b.shape[1]), mesh=mesh,
                                  axis=axis, cache=cache,
                                  backend=backend).schedule
    sched = Schedule() if schedule is None else schedule
    axis_size = mesh.shape[axis]
    mode = sched.collective or "nnz_rs"
    if mode == "row":
        rows, cols, vals, _ = partition_rows_coo(csr, axis_size,
                                                 sched.nnz_tile)
    else:
        rows, cols, vals, _ = partition_nnz_coo(csr, axis_size,
                                                sched.nnz_tile)
    if sched.value_dtype is not None:
        from ..tune.measure import _storage_feed

        vals, b = _storage_feed(vals, b, sched.value_dtype)
    return spmm_shard_map(rows, cols, vals, b, n_rows=csr.shape[0],
                          mesh=mesh, axis=axis, mode=mode,
                          schedule=sched.replace(collective=mode))


# ---------------------------------------------------------------------------
# Distributed fused sparse attention
# ---------------------------------------------------------------------------


def _local_attention(rows, cols, q, k, v, *, n_rows, scale, sched,
                     bias=None):
    """Run the fused kernel over a shard's lanes at height ``n_rows`` + 1
    phantom row (pad lanes land there; the caller crops it)."""
    strategy = (sched.strategy
                if sched.strategy in ("segment", "accumulate")
                else "segment")
    nnz_local = int(rows.shape[0])
    pad = round_up(max(nnz_local, 1), sched.nnz_tile) - nnz_local
    if pad:  # extra pad lanes join the phantom row too
        rows = jnp.concatenate(
            [rows, jnp.full((pad,), n_rows, jnp.int32)])
        cols = jnp.concatenate([cols, jnp.zeros((pad,), jnp.int32)])
        if bias is not None:
            bias = jnp.concatenate([bias, jnp.zeros((pad,), bias.dtype)])
    # the kernel is node-major, (n, H, ·); the shards' algebra head-major
    q_ph = jnp.pad(q, ((0, 0), (0, 1), (0, 0)))
    out, m, l = fused_sparse_attention(
        rows, cols, *(jnp.moveaxis(x, 0, 1) for x in (q_ph, k, v)),
        n_rows=n_rows + 1, nnz=int(rows.shape[0]), nnz_tile=sched.nnz_tile,
        scale=scale, group_size=sched.group_size, strategy=strategy,
        bias=bias)
    return (jnp.moveaxis(out, 1, 0)[:, :n_rows], m.T[:, :n_rows],
            l.T[:, :n_rows])


def _combine_partials(out_s, m_s, l_s, axis, *, scatter):
    """Merge per-shard online-softmax partials over the mesh axis.

    Each shard holds (out_s, m_s, l_s) of its lane subset at full height
    (out_s already normalized by its local l_s).  The global result
    rescales every shard to the global row max and sums: the same
    m/l/alpha algebra the kernel runs per nnz tile, one level up.
    ``scatter=True`` is the segment realization — l and the accumulator
    combine with reduce-scatter so each shard finalizes its row block
    (the row max still needs the cheap (H, R) all-reduce pmax).
    """
    m = jax.lax.pmax(m_s, axis)
    scale = jnp.where(m_s <= NEG_INF / 2, 0.0, jnp.exp(m_s - m))
    lw = l_s * scale                      # (H, R)
    acc = out_s * lw[..., None]           # (H, R, dv)
    if scatter:
        lw = jax.lax.psum_scatter(lw, axis, scatter_dimension=1,
                                  tiled=True)
        acc = jax.lax.psum_scatter(acc, axis, scatter_dimension=1,
                                   tiled=True)
    else:
        lw = jax.lax.psum(lw, axis)
        acc = jax.lax.psum(acc, axis)
    return acc / jnp.maximum(lw, 1e-30)[..., None]


def dist_attention_shard_map(rows, cols, q, k, v, *, n_rows: int, mesh,
                             axis: str, mode: str | None = None,
                             schedule: Schedule | None = None,
                             scale: float | None = None, bias=None):
    """Sparse attention under shard_map with the row/nnz_ar/nnz_rs trio.

    rows/cols: (nnz_pad,) adjacency lane streams built by the partition
    helpers with ``phantom_row=True`` (pad lanes have no zero value, so
    they target the phantom row and are cropped, never masked).  q/k/v
    are head-major — q (H, n_rows, d), k (H, n_kv, d), v (H, n_kv, dv)
    2-D inputs are treated as one head.

    row      rows pre-bucketed per shard (local indices,
             :func:`partition_rows_coo`), q row-sharded, k/v replicated;
             no collectives — each shard owns its output rows whole.
    nnz_*    lanes nnz-partitioned (:func:`partition_nnz_coo`), q/k/v
             replicated; shards compute full-height online-softmax
             partials and merge them with psum (nnz_ar) or psum_scatter
             (nnz_rs) over the per-row statistics — the same
             rescale-and-sum algebra the kernel's nnz-tile carry runs,
             elevated to the mesh.

    Returns out (H, n_rows, dv) (squeezed back to 2-D for 2-D inputs),
    row-sharded over ``axis`` for row/nnz_rs, replicated for nnz_ar.
    """
    sched = Schedule() if schedule is None else schedule
    if sched.kernel != "eb":  # attention tiling is eb-shaped
        sched = Schedule(collective=sched.collective)
    mode = _resolve_collective(mode, schedule)
    axis_size = mesh.shape[axis]
    squeeze = q.ndim == 2
    if squeeze:
        q, k, v = q[None], k[None], v[None]
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    has_bias = bias is not None
    lane_specs = (P(axis), P(axis)) + ((P(axis),) if has_bias else ())

    if mode == "row":
        if n_rows % axis_size:
            raise ValueError(
                f"row mode needs n_rows ({n_rows}) divisible by the "
                f"axis size ({axis_size})")
        block = n_rows // axis_size

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=lane_specs + (P(None, axis, None), P(), P()),
            out_specs=P(None, axis, None),
        )
        def _row(r, c, *rest):
            b = rest[0] if has_bias else None
            qq, kk, vv = rest[-3:]
            out, _, _ = _local_attention(r, c, qq, kk, vv, n_rows=block,
                                         scale=scale, sched=sched, bias=b)
            return out

        args = (rows, cols) + ((bias,) if has_bias else ()) + (q, k, v)
        out = _row(*args)
    else:
        if mode == "nnz_rs" and n_rows % axis_size:
            raise ValueError(
                f"nnz_rs mode needs n_rows ({n_rows}) divisible by the "
                f"axis size ({axis_size})")

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=lane_specs + (P(), P(), P()),
            out_specs=(P(None, axis, None) if mode == "nnz_rs" else P()),
        )
        def _nnz(r, c, *rest):
            b = rest[0] if has_bias else None
            qq, kk, vv = rest[-3:]
            out_s, m_s, l_s = _local_attention(
                r, c, qq, kk, vv, n_rows=n_rows, scale=scale, sched=sched,
                bias=b)
            return _combine_partials(out_s, m_s, l_s, axis,
                                     scatter=mode == "nnz_rs")

        args = (rows, cols) + ((bias,) if has_bias else ()) + (q, k, v)
        out = _nnz(*args)

    return out[0] if squeeze else out
