"""Empirical schedule search over the atomic-parallelism space.

The paper's dgSPARSE result (1.6x–2.3x, Table 4) comes from *tuning*
``<groupSz, blockSz, tileSz, workerDim>``, not from a fixed heuristic.
:func:`tune_schedule` makes that search a library call.  Since the §14
refactor the search loop itself lives in :func:`repro.tune.driver.drive`
— this module only *declares* the SpMM / segment-reduce / distributed
spaces (which axes, which cost model, which cache key) and hands them to
the driver:

1. **warm start** — rank :func:`~repro.core.candidate_schedules` by the
   static cost model (:func:`~repro.core.predict_cost`), prune points
   whose working set overflows VMEM;
2. **measure** — time the top-k candidates plus the selector's own pick
   (``Schedule.auto`` is always in the measured pool, so the tuned
   choice can never lose to it beyond timing noise);
3. **dtype axis** — re-measure the winner under each narrow value dtype
   (``DEFAULT_VALUE_DTYPES``) whose storage-parity error fits the
   ``error_budget`` — precision is a tuned knob, not a global switch
   (DESIGN.md §13);
4. **hillclimb** — take x2 / /2 steps on ``group_size`` and the tile
   fields around the measured winner until no neighbor improves;
5. **cache** — persist the winner in the :class:`~.cache.ScheduleCache`
   under the matrix fingerprint, so serving/training loops tune once and
   replay (a hit performs *zero* measurements).

``measure=`` is injectable (schedule -> seconds) for tests and for
calibration replays.
"""
from __future__ import annotations

from typing import Callable, List, Optional

import numpy as np

from ..core import (COLLECTIVES, Schedule, candidate_schedules, predict_cost,
                    predict_dist_cost, select_schedule)
from ..kernels.ops import schedule_fits_vmem
from ..sparse.random import matrix_stats
from .cache import ScheduleCache, cache_key, default_cache
from .driver import TuneResult, _replay, drive
from .measure import measure_dist_schedule, measure_schedule, time_fn
from .space import (CollectiveAxis, EpilogueAxis, SearchContext, SearchSpace,
                    SkewAxis, StrategyAxis, TilingAxis, ValueDtypeAxis,
                    schedule_key)

__all__ = [
    "DEFAULT_VALUE_DTYPES",
    "DIST_VALUE_DTYPES",
    "TuneResult",
    "cached_or_auto",
    "schedule_key",
    "tune_dist_spmm",
    "tune_schedule",
    "tune_segment_reduce",
]

#: Dtype-axis candidates measured by default (DESIGN.md §13).  fp8 is
#: deliberately absent: on backends without native fp8 it silently
#: degrades to bf16 (``core.dtypes.storage_dtype``), so tuning would
#: just measure bf16 twice; pass ``value_dtypes=("float8_e4m3fn", ...)``
#: explicitly on hardware that has it.
DEFAULT_VALUE_DTYPES = ("bfloat16", "float16", "int8")

#: Dtype-axis candidates for the *distributed* search.  int8 is
#: excluded: the shard-local kernel consumes partitioned GroupedCOO
#: shards, and the int8 path needs the per-row scales a CSR/
#: QuantizedCSR carries (``kops.spmm`` rejects the combination).
DIST_VALUE_DTYPES = ("bfloat16", "float16")


def _feasible(cands: List[Schedule], stats: dict) -> List[Schedule]:
    """The candidates whose launch fits VMEM (an eb one in the layout it
    runs, windowed where the resident one does not fit, so eb candidates
    always stay); ``ValueError`` where none does, rather than a schedule
    the compiler must refuse."""
    kept = [s for s in cands
            if schedule_fits_vmem(s, n_rows=stats["n_rows"],
                                  n_cols=stats["n_cols"],
                                  row_max=stats["row_max"])]
    if not kept:
        raise ValueError(
            f"no candidate schedule fits VMEM for a {stats['n_rows']} x "
            f"{stats['n_cols']} matrix: {[str(s) for s in cands]}")
    return kept


def _dtype_parity_error(csr, n_dense_cols: int, vd: str) -> float:
    """Relative L2 error of the ``vd`` storage analogue vs the f32
    oracle on a deterministic dense B (the same ``_dense_b`` the
    runners feed).

    Measures storage-precision loss only — the analogue accumulates in
    f32 like the kernels (``upcast_f32`` contract), so the number is a
    property of (matrix, dtype), independent of tiling/strategy, and is
    computed once per dtype per tuning run.  int8 goes through the real
    quantize/dequantize path (per-row symmetric scales)."""
    import jax.numpy as jnp

    from ..core.dtypes import operand_dtype, storage_dtype
    from ..kernels import ref
    from .measure import _dense_b

    coo = csr.tocoo()
    b = _dense_b(csr, n_dense_cols)
    out32 = ref.spmm_coo_ref(coo.rows, coo.cols, coo.vals, b, csr.shape[0])
    if vd == "int8":
        vals = csr.quantized().dequantize().tocoo().vals
    else:
        vals = coo.vals.astype(storage_dtype(vd))
    out = ref.spmm_coo_ref(coo.rows, coo.cols, vals,
                           b.astype(operand_dtype(vd)), csr.shape[0])
    num = float(jnp.linalg.norm((out - out32).ravel()))
    den = float(jnp.linalg.norm(out32.ravel()))
    return num / (den + 1e-12)


def _storage_parity(ctx: SearchContext, vd: str) -> float:
    """The :class:`ValueDtypeAxis` admission gate for CSR workloads."""
    return _dtype_parity_error(ctx.workload, ctx.n_dense_cols, vd)


def _vmem_filter(ctx: SearchContext, cands: List[Schedule]) -> List[Schedule]:
    return _feasible(cands, ctx.stats)


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------


def tune_schedule(
    csr,
    n_dense_cols: int,
    *,
    cache: Optional[ScheduleCache] = None,
    top_k: int = 4,
    hill_steps: int = 3,
    measure: Optional[Callable[[Schedule], float]] = None,
    warmup: Optional[int] = None,
    iters: Optional[int] = None,
    backend: Optional[str] = None,
    epilogue=None,
    value_dtypes: Optional[tuple] = None,
    error_budget: float = 0.05,
) -> TuneResult:
    """Empirically pick the best schedule for ``csr @ B`` (B with
    ``n_dense_cols`` columns); see the module docstring for the phases.

    cache       ScheduleCache to consult/update (default: the process
                cache at ``REPRO_TUNE_CACHE``); a hit replays with zero
                measurements.
    top_k       cost-model-ranked candidates to measure beyond the
                selector's pick.
    hill_steps  max hillclimb rounds around the measured winner.
    measure     override objective ``schedule -> seconds`` (tests,
                calibration replays); default wall-clocks the jitted
                schedule analogue via ``tune.measure``.
    epilogue    fused :class:`~repro.core.Epilogue` the workload will run
                — attached to every measured candidate so the fused work
                is *part of the objective*, and folded into the cache key
                (an epilogued workload never replays a plain record or
                vice versa).  The returned/tuned schedule carries it.
    value_dtypes  dtype-axis candidates (DESIGN.md §13); default
                :data:`DEFAULT_VALUE_DTYPES`, ``()`` disables the axis.
                Each candidate is admitted only if its storage-parity
                error vs the f32 oracle is within ``error_budget``, then
                measured as a variant of the pool winner (the dtype
                rescales traffic uniformly across tilings, so crossing
                the full grid with every dtype would waste measurements).
    error_budget  max relative L2 parity error an admitted narrow dtype
                may introduce (default 5%).
    """
    if cache is None:
        cache = default_cache(backend)
    if epilogue is not None and epilogue.is_noop:
        epilogue = None
    key = cache_key(csr, n_dense_cols)
    if epilogue is not None:
        key = f"{key}|ep:{epilogue.tag}"
    hit = _replay(cache, key)
    if hit is not None:
        return hit

    stats = matrix_stats(csr)
    if measure is None:
        def measure(s: Schedule) -> float:
            return measure_schedule(csr, n_dense_cols, s,
                                    warmup=warmup, iters=iters)

    def _with_ep(s: Schedule) -> Schedule:
        return s if epilogue is None else s.replace(epilogue=epilogue)

    if value_dtypes is None:
        value_dtypes = DEFAULT_VALUE_DTYPES
    # the SpMM space: the paper's (strategy × tiling) core, the skew
    # axis (§11) and the parity-gated dtype axis (§13); hillclimb moves
    # are vmem-pruned like the candidate grid
    space = SearchSpace(
        (StrategyAxis(), TilingAxis(), SkewAxis(),
         ValueDtypeAxis(value_dtypes, error_budget=error_budget,
                        parity=_storage_parity),
         EpilogueAxis()),
        key_fn=schedule_key,
        neighbor_filter=_vmem_filter,
    )
    ctx = SearchContext(stats=stats, n_dense_cols=n_dense_cols, workload=csr)
    ranked = space.rank(ctx, _feasible(candidate_schedules(n_dense_cols),
                                       stats),
                        lambda s: predict_cost(stats, s, n_dense_cols))
    ranked = [_with_ep(s) for s in ranked]
    seeds = [_with_ep(select_schedule(stats, n_dense_cols))]
    return drive(space, ctx, cache=cache, key=key, measure=measure,
                 seeds=seeds, ranked=ranked, top_k=top_k,
                 hill_steps=hill_steps)


def cached_or_auto(csr, n_dense_cols: int, *,
                   cache: Optional[ScheduleCache] = None,
                   backend: Optional[str] = None,
                   key: Optional[str] = None) -> Schedule:
    """Cache-hit schedule if one exists, else the static selector's pick —
    **never measures**.  This is the serving-path resolver: a latency-
    sensitive loop consults tuning done ahead of time (by
    :func:`tune_schedule`, e.g. through ``ServeEngine.prepare_sparse``)
    and must not stall a request on a tuning run."""
    if cache is None:
        cache = default_cache(backend)
    rec = cache.get(key if key is not None
                    else cache_key(csr, n_dense_cols))
    if rec is not None:
        return rec.schedule
    return Schedule.auto(matrix_stats(csr), n_dense_cols)


# ---------------------------------------------------------------------------
# segment_reduce tuning (no CSR matrix: segments play the role of rows)
# ---------------------------------------------------------------------------


def tune_segment_reduce(
    seg_ids,
    n_cols: int,
    num_segments: int,
    *,
    cache: Optional[ScheduleCache] = None,
    measure: Optional[Callable[[Schedule], float]] = None,
    warmup: Optional[int] = None,
    iters: Optional[int] = None,
    backend: Optional[str] = None,
) -> TuneResult:
    """Tune (tile, group_size, strategy) for a standalone segment reduce.

    The segment-length histogram stands in for the row-length histogram
    in the fingerprint (keys prefixed ``segred:``); candidates are the
    EB half of the grid (the RB kernel has no segment-reduce analogue).
    The objective times the *actual* segment-reduce kernel wrapper —
    unlike SpMM tuning there is no cheaper analogue that still observes
    the tile axis, and the kernel is the op being tuned.  The space is
    exhaustive (every grid point measured, no hillclimb), so the driver
    runs with ``top_k=None, hill_steps=0``."""
    from .cache import fingerprint_from_lengths

    seg = np.asarray(seg_ids)
    t = int(seg.shape[0])
    lengths = np.bincount(seg, minlength=max(num_segments, 1))
    fp = fingerprint_from_lengths(lengths, (num_segments, n_cols), t)
    key = f"segred:{fp}|N{n_cols}"

    if cache is None:
        cache = default_cache(backend)
    hit = _replay(cache, key)
    if hit is not None:
        return hit

    if measure is None:
        import jax
        import jax.numpy as jnp

        from ..kernels.segment_reduce import segment_reduce as _segred

        data = jax.random.normal(jax.random.PRNGKey(0), (t, n_cols))
        seg_j = jnp.asarray(seg, jnp.int32)

        def measure(s: Schedule) -> float:
            def fn(ss, d):
                return _segred(ss, d, num_segments=num_segments,
                               tile=s.nnz_tile, group_size=s.group_size,
                               strategy=s.strategy)

            return time_fn(fn, seg_j, data, warmup=warmup, iters=iters)

    space = SearchSpace((StrategyAxis(), TilingAxis()), key_fn=schedule_key)
    pool = [Schedule("eb", nnz_tile=tile, group_size=g, strategy=st)
            for tile in (128, 512)
            for g in (8, 32)
            for st in ("segment", "accumulate")]
    return drive(space, SearchContext(), cache=cache, key=key,
                 measure=measure, ranked=pool)


# ---------------------------------------------------------------------------
# Distributed tuning: one search over (local tiling × collective × dtype)
# ---------------------------------------------------------------------------


def _feasible_collectives(stats: dict, axis_size: int) -> List[str]:
    """Collective modes the mesh/shape combination can realize: 'nnz_ar'
    always works; 'row' and 'nnz_rs' finalize a row block per shard, so
    they need ``n_rows % axis_size == 0`` (DESIGN.md §12)."""
    modes = ["nnz_ar"]
    if axis_size <= 1 or stats["n_rows"] % axis_size == 0:
        modes += ["nnz_rs", "row"]
    return modes


def tune_dist_spmm(
    csr,
    n_dense_cols: int,
    *,
    mesh,
    axis: str,
    cache: Optional[ScheduleCache] = None,
    top_k: int = 2,
    hill_steps: int = 2,
    measure: Optional[Callable[[Schedule], float]] = None,
    warmup: Optional[int] = None,
    iters: Optional[int] = None,
    backend: Optional[str] = None,
    value_dtypes: Optional[tuple] = None,
    error_budget: float = 0.05,
) -> TuneResult:
    """One empirical search over (kernel tiling × collective mode ×
    value dtype) for a sharded ``csr @ B`` on ``mesh`` — the tentpole of
    DESIGN.md §12 extended by §14's joint axis search: the wire strategy
    *and* the storage precision are :class:`Schedule` axes, not separate
    knobs, so the tuner can trade local tile shape against collective
    bytes against value-traffic width in a single objective
    (``measure_dist_schedule`` times the real shard_map program).

    Candidates are the top-ranked *local* eb tilings (the shard-local
    kernel only takes the eb path) crossed with every feasible collective
    mode, pre-ranked by :func:`~repro.core.predict_dist_cost` — the
    per-shard cost model plus the ``WIRE_COST_WEIGHT`` wire term and the
    ``shard_nnz`` straggler factor — then measured.  The parity-gated
    narrow dtypes (:data:`DIST_VALUE_DTYPES`; ``value_dtypes=()``
    recovers the single-axis search) are measured as variants of the
    pool winner with its collective held, and a short hillclimb refines
    the winner's local axes with the collective held fixed (a collective
    flip re-partitions the operands, so it is a pool move, not a
    neighbor move).  The cache key folds in the mesh extent:
    ``dist:<fingerprint>|mesh:<P>`` — the same matrix on a different
    mesh is a different tuning problem.
    """
    axis_size = int(mesh.shape[axis])
    if cache is None:
        cache = default_cache(backend)
    key = f"dist:{cache_key(csr, n_dense_cols)}|mesh:{axis_size}"
    hit = _replay(cache, key)
    if hit is not None:
        return hit

    from ..sparse.distributed import shard_nnz_counts

    stats = matrix_stats(csr)
    if measure is None:
        def measure(s: Schedule) -> float:
            return measure_dist_schedule(csr, n_dense_cols, s, mesh=mesh,
                                         axis=axis, warmup=warmup,
                                         iters=iters)

    if value_dtypes is None:
        value_dtypes = DIST_VALUE_DTYPES
    modes = _feasible_collectives(stats, axis_size)
    # the distributed space: no skew axis — ``_local_spmm`` strips skew
    # from shard-local schedules, so a skew point would measure the same
    # program twice
    space = SearchSpace(
        (StrategyAxis(), TilingAxis(), CollectiveAxis(modes),
         ValueDtypeAxis(value_dtypes, error_budget=error_budget,
                        parity=_storage_parity),
         EpilogueAxis()),
        key_fn=schedule_key,
        neighbor_filter=lambda c, cands: [
            s for s in _feasible(cands, c.stats)
            if s.collective in COLLECTIVES],
    )
    ctx = SearchContext(stats=stats, n_dense_cols=n_dense_cols,
                        axis_size=axis_size, workload=csr)

    eb = [s for s in _feasible(candidate_schedules(n_dense_cols), stats)
          if s.kernel == "eb"]
    eb.sort(key=lambda s: predict_cost(stats, s, n_dense_cols))
    auto = select_schedule(stats, n_dense_cols)
    seeds = ([auto] if auto.kernel == "eb" else []) + eb[:max(1, top_k)]
    pool = space.rank(ctx, space.cross(ctx, seeds),
                      lambda s: predict_dist_cost(
                          stats, s, n_dense_cols, axis_size=axis_size,
                          shard_nnz=shard_nnz_counts(csr, axis_size,
                                                     s.collective)))
    return drive(space, ctx, cache=cache, key=key, measure=measure,
                 ranked=pool, hill_steps=hill_steps)
