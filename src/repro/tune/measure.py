"""Measurement layer shared by the autotuner and the benchmark harness.

``time_fn`` is the single wall-clock timer in the repo — the paper-table
benchmarks (``benchmarks/_util``) re-export it from here, and the tuner
(``tune.search``) calls it directly, so a tuned number and a benchmarked
number come from the same instrument.  The iteration count is
env-tunable (``REPRO_BENCH_ITERS`` / ``REPRO_BENCH_WARMUP``) so CI smoke
runs can trade variance for wall time.

The schedule runners build a jitted pure-JAX analogue of each kernel
schedule — XLA compiles a genuinely different program per schedule point
(group size, strategy, tiling all change the compiled structure), so
relative effects track the paper's axes; absolute numbers are
backend-specific (DESIGN.md changed assumption 5).
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import GroupReduceStrategy, Schedule, segment_group_reduce
from ..kernels import ref

__all__ = [
    "bench_iters",
    "bench_warmup",
    "time_fn",
    "make_eb_runner",
    "make_rb_runner",
    "make_runner",
    "make_dist_runner",
    "measure_schedule",
    "measure_dist_schedule",
]


def bench_iters(default: int = 7) -> int:
    """Timing iterations per measurement; override with REPRO_BENCH_ITERS
    (CI smoke sets a small value to stay under its time budget)."""
    return max(1, int(os.environ.get("REPRO_BENCH_ITERS", default)))


def bench_warmup(default: int = 2) -> int:
    """Warmup iterations per measurement; override with
    REPRO_BENCH_WARMUP (CI smoke lowers it to fit its time budget)."""
    return max(0, int(os.environ.get("REPRO_BENCH_WARMUP", default)))


def time_fn(fn, *args, warmup: int | None = None,
            iters: int | None = None, cap_env: bool = True) -> float:
    """Median seconds/call of a jitted fn (blocks on results).

    ``REPRO_BENCH_ITERS`` / ``REPRO_BENCH_WARMUP`` supply defaults and
    *cap* explicit arguments, so CI smoke bounds total bench time without
    touching call sites.  ``cap_env=False`` exempts a measurement from
    the caps — for fixed-workload yardsticks that must be comparable
    across runs (the ``probe/runner_speed`` row)."""
    if warmup is None:
        warmup = bench_warmup()
    elif cap_env and "REPRO_BENCH_WARMUP" in os.environ:
        warmup = min(warmup, bench_warmup())
    if iters is None:
        iters = bench_iters()
    elif cap_env and "REPRO_BENCH_ITERS" in os.environ:
        iters = max(1, min(iters, bench_iters()))
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


# ------------------------------------------------------------------------
# Schedule executor: pure-JAX analogue of each kernel schedule, jitted so
# XLA compiles a genuinely different program per schedule point.
# ------------------------------------------------------------------------


def _dense_b(csr, n_dense):
    return jax.random.normal(jax.random.PRNGKey(0), (csr.shape[1], n_dense))


def _epilogue_args(epilogue, n_rows, n_dense):
    """Synthesized epilogue operands for measurement (the tuner measures
    the fused work a real workload would run — DESIGN.md §8)."""
    if epilogue is None or epilogue.is_noop:
        return None, None
    key = jax.random.PRNGKey(1)
    bias = (jax.random.normal(key, (n_dense,))
            if epilogue.bias else None)
    res = (jax.random.normal(key, (n_rows, n_dense))
           if epilogue.residual else None)
    return bias, res


def _apply_epilogue(out, epilogue, bias, res):
    if epilogue is None or epilogue.is_noop:
        return out
    return epilogue.apply(out, bias=bias, residual=res)


def make_eb_runner(csr, n_dense, *, group_size: int, strategy: str,
                   nnz_tile: int = 256, epilogue=None,
                   split_threshold: int | None = None,
                   merge_threshold: int | None = None,
                   value_dtype: str | None = None):
    """Jitted pure-JAX analogue of the EB kernel schedule.

    With split/merge thresholds the feed is the two-level skew layout
    (DESIGN.md §11): the leading heavy region holds single-row groups,
    so it reduces with a cheap per-group sum + leader ``segment_sum``
    (the 'parallel' realization's cost shape) instead of the full
    segment-group machinery — the measured program genuinely changes
    with the thresholds, which is what lets the tuner prefer them on
    power-law inputs.

    ``value_dtype`` (DESIGN.md §13) narrows the *fed arrays* — narrow
    floats cast the value stream and B; 'int8' feeds codes + per-row
    scales with the dequant inside the measured program — so XLA
    compiles a genuinely narrower program and the tuner's dtype axis
    measures real traffic, not a relabeled f32 run."""
    scales = None
    if value_dtype == "int8":
        q = csr.quantized()
        scales, csr_feed = q.scales, q.csr
    else:
        csr_feed = csr
    tile = max(nnz_tile, group_size)
    g = csr_feed.grouped(tile, group_size=group_size,
                         split_threshold=split_threshold,
                         merge_threshold=merge_threshold)
    n_rows = csr.shape[0]
    hn = g.heavy_tiles * tile  # static heavy-region lane count
    bias, res = _epilogue_args(epilogue, n_rows, n_dense)

    def _run(rows, cols, vals, b):
        v32 = vals.astype(jnp.float32)
        if scales is not None:
            v32 = v32 * jnp.take(scales, rows)
        partial = v32[:, None] * jnp.take(
            b.astype(jnp.float32), cols, axis=0)
        if strategy == GroupReduceStrategy.ACCUMULATE.value:
            out = jax.ops.segment_sum(partial, rows, num_segments=n_rows)
        else:
            tail_p, tail_r = partial, rows
            out = jnp.zeros((n_rows, partial.shape[1]), jnp.float32)
            if hn:
                # heavy region: groups are single-row, so a plain
                # within-group sum + one scatter per group (the
                # 'parallel' realization) replaces the one-hot reduce
                gsum = partial[:hn].reshape(-1, group_size,
                                            partial.shape[1]).sum(1)
                leaders = rows[:hn].reshape(-1, group_size)[:, 0]
                out = out + jax.ops.segment_sum(gsum, leaders,
                                                num_segments=n_rows)
                tail_p, tail_r = partial[hn:], rows[hn:]
            if tail_p.shape[0]:
                # any registered strategy name dispatches via the registry
                out = out + segment_group_reduce(tail_p, tail_r, n_rows,
                                                 group_size=group_size,
                                                 strategy=strategy)
        return _apply_epilogue(out, epilogue, bias, res)

    fn = jax.jit(_run)
    vals_feed, b_feed = _storage_feed(g.vals, _dense_b(csr, n_dense),
                                      value_dtype)
    args = (g.rows, g.cols, vals_feed, b_feed)
    return fn, args


def _storage_feed(vals, b, value_dtype):
    """Cast (vals, B) to the schedule's storage dtypes — the runner's
    compiled program then *reads narrow*, which is the effect the dtype
    axis is tuning.  int8 feeds are pre-quantized by the caller."""
    if value_dtype is None:
        return vals, b
    from ..core.dtypes import operand_dtype, storage_dtype

    if value_dtype != "int8":
        vals = vals.astype(storage_dtype(value_dtype))
    return vals, b.astype(operand_dtype(value_dtype))


def make_rb_runner(csr, n_dense, *, row_tile: int = 8,
                   width: int | None = None, epilogue=None,
                   value_dtype: str | None = None):
    """Jitted (fn, args) measuring the row-balanced (ELL) SpMM analogue
    with the epilogue folded into the measured program (``value_dtype``
    narrows the fed arrays as in :func:`make_eb_runner`)."""
    scales = None
    if value_dtype == "int8":
        q = csr.quantized()
        ell = q.csr.ell(row_tile=row_tile, width=width)
        scales = jnp.pad(
            q.scales, (0, ell.n_rows_padded - csr.shape[0]),
            constant_values=1.0)
    else:
        ell = csr.ell(row_tile=row_tile, width=width)
    n_rows = csr.shape[0]
    bias, res = _epilogue_args(epilogue, n_rows, n_dense)

    def _run(ecols, evals, b):
        ev = evals.astype(jnp.float32)
        if scales is not None:
            ev = ev * scales[:, None]
        return _apply_epilogue(ref.spmm_ell_ref(ecols, ev, b, n_rows),
                               epilogue, bias, res)

    fn = jax.jit(_run)
    vals_feed, b_feed = _storage_feed(ell.vals, _dense_b(csr, n_dense),
                                      value_dtype)
    args = (ell.cols, vals_feed, b_feed)
    return fn, args


def make_runner(csr, n_dense: int, sched: Schedule):
    """Runner for an arbitrary :class:`Schedule` (dispatch on kernel);
    the schedule's epilogue and value dtype are part of the measured
    program."""
    if sched.kernel == "eb":
        return make_eb_runner(csr, n_dense, group_size=sched.group_size,
                              strategy=sched.strategy,
                              nnz_tile=sched.nnz_tile,
                              epilogue=sched.epilogue,
                              split_threshold=sched.split_threshold,
                              merge_threshold=sched.merge_threshold,
                              value_dtype=sched.value_dtype)
    return make_rb_runner(csr, n_dense, row_tile=sched.row_tile,
                          epilogue=sched.epilogue,
                          value_dtype=sched.value_dtype)


def measure_schedule(csr, n_dense: int, sched: Schedule, *,
                     warmup: int | None = None,
                     iters: int | None = None) -> float:
    """Seconds/call of ``sched`` applied to ``csr @ B`` with ``n_dense``
    dense columns — the tuner's objective function."""
    fn, args = make_runner(csr, n_dense, sched)
    return time_fn(fn, *args, warmup=warmup, iters=iters)


# ------------------------------------------------------------------------
# Distributed measurement: the real shard_map program under a real mesh
# ------------------------------------------------------------------------


def make_dist_runner(csr, n_dense: int, sched: Schedule, *, mesh,
                     axis: str):
    """Jitted (fn, args) running ``spmm_shard_map`` under ``sched`` on a
    *real* mesh (the forced-host-device mesh in CI) — unlike the
    single-device analogues there is no cheaper stand-in that still
    observes the collective axis: the wire mode only exists in the
    compiled SPMD program, so the objective is the program itself.
    Partitioning (host-side) happens here, outside the timed region.
    A narrow ``sched.value_dtype`` narrows the fed value/operand arrays
    (:func:`_storage_feed`) so the joint collective × dtype search times
    the storage width it is choosing."""
    from ..sparse.distributed import (partition_nnz_coo, partition_rows_coo,
                                      spmm_shard_map)

    axis_size = mesh.shape[axis]
    if (sched.collective or "nnz_rs") == "row":
        rows, cols, vals, _ = partition_rows_coo(csr, axis_size,
                                                 sched.nnz_tile)
    else:
        rows, cols, vals, _ = partition_nnz_coo(csr, axis_size,
                                                sched.nnz_tile)

    def _run(r, c, v, b):
        return spmm_shard_map(r, c, v, b, n_rows=csr.shape[0], mesh=mesh,
                              axis=axis, schedule=sched)

    vals_feed, b_feed = _storage_feed(vals, _dense_b(csr, n_dense),
                                      sched.value_dtype)
    args = (rows, cols, vals_feed, b_feed)
    return _run, args


def measure_dist_schedule(csr, n_dense: int, sched: Schedule, *, mesh,
                          axis: str, warmup: int | None = None,
                          iters: int | None = None) -> float:
    """Seconds/call of the distributed schedule point (local tiling +
    ``sched.collective`` wire mode) — ``tune_dist_spmm``'s objective."""
    fn, args = make_dist_runner(csr, n_dense, sched, mesh=mesh, axis=axis)
    return time_fn(fn, *args, warmup=warmup, iters=iters)
