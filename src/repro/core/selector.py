"""Data-aware schedule selector (DA-SpMM-style, Sgap §7.2 Table 5).

Given matrix statistics and the dense-column count N, pick an
(atomic-parallelism) schedule. The decision mirrors the paper's findings:

* few dense columns (N <= 8): *balance*-bound -> nnz-split (EB) wins when
  row lengths are skewed; group size should shrink when rows are short
  (challenge 1: parallelism waste).
* many dense columns: *workload*-bound -> row-split (RB) with wide column
  tiles reuses the loaded sparse row across columns.
* segment strategy when writeback targets are runtime-dependent (high CV),
  parallel strategy when rows are long and regular.

Also exposes :func:`predict_cost` — the cost model used here and by the
empirical autotuner (``repro.tune``).
The model is a weighted sum of four raw terms (:func:`cost_terms`); the
weights default to the hand-set napkin values but are *calibratable*:
``repro.tune.calibrate`` least-squares fits them against measured
timings and installs the fit via :func:`set_cost_weights`.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

from .schedule import Schedule
from .segment_group import group_waste_fraction

__all__ = [
    "select_schedule",
    "predict_cost",
    "predict_dist_cost",
    "collective_cost_terms",
    "candidate_schedules",
    "cost_terms",
    "COST_TERM_NAMES",
    "DEFAULT_COST_WEIGHTS",
    "WIRE_COST_WEIGHT",
    "get_cost_weights",
    "set_cost_weights",
]

COST_TERM_NAMES = ("work", "waste", "writeback", "gather")

#: Hand-set napkin weights (the pre-calibration model): cost =
#: work + waste + 2*writeback + 0.25*gather.
DEFAULT_COST_WEIGHTS: Tuple[float, float, float, float] = (1.0, 1.0, 2.0,
                                                           0.25)

_cost_weights: Tuple[float, float, float, float] = DEFAULT_COST_WEIGHTS


def get_cost_weights() -> Tuple[float, float, float, float]:
    """The active (work, waste, writeback, gather) term weights."""
    return _cost_weights


def set_cost_weights(weights: Sequence[float] | None) -> None:
    """Install calibrated term weights (``None`` restores the defaults).

    Affects every subsequent :func:`predict_cost` / ``Schedule.auto``
    call — this is how measured tuning data feeds back into the static
    selector (``repro.tune.calibrate``).
    """
    global _cost_weights
    if weights is None:
        _cost_weights = DEFAULT_COST_WEIGHTS
        return
    w = tuple(float(x) for x in weights)
    if len(w) != 4:
        raise ValueError(f"need 4 weights {COST_TERM_NAMES}, got {len(w)}")
    if any(x < 0 for x in w) or not any(x > 0 for x in w):
        raise ValueError(f"weights must be >= 0 with at least one > 0: {w}")
    _cost_weights = w


def candidate_schedules(n_dense_cols: int) -> list[Schedule]:
    """The tuning grid from the paper's dgSPARSE experiment, TPU-mapped:
    <groupSz, blockSz, tileSz, workerDimR> -> <G, nnz/row tile, col tile>."""
    cands = []
    col_tile = max(8, min(128, n_dense_cols))
    for g in (8, 16, 32, 64):
        for nnz_tile in (128, 256, 512):
            if nnz_tile % g:
                continue
            cands.append(Schedule("eb", nnz_tile=nnz_tile,
                                  col_tile=col_tile, group_size=g,
                                  strategy="segment"))
    for row_tile in (8, 16, 32):
        cands.append(Schedule("rb", row_tile=row_tile,
                              col_tile=col_tile, strategy="parallel"))
    return cands


def cost_terms(stats: Dict, sched: Schedule,
               n_dense_cols: int) -> Tuple[float, float, float, float]:
    """The four raw cost-model terms (lower = better, unweighted):

    work        nnz * C multiply-adds (same for every schedule);
    waste       zero-extension padding lanes (rb: rows padded to ELL width;
                eb: short rows padded to the group width) — grows with G;
    writeback   segment writeback events: one per row touched plus one
                carry per group boundary (eb) — the carry part *shrinks*
                with G, which is the paper's reason to widen groups; rb
                pays exactly one per row;
    gather      dense-row gather traffic ~ nnz * col_tile.

    waste and writeback pull G in opposite directions, so the
    waste:writeback weight ratio (calibratable — ``repro.tune``) decides
    the group size, exactly the machine-dependent trade the paper tunes.

    A narrow ``sched.value_dtype`` (DESIGN.md §13) rescales the two
    traffic-shaped terms by itemsize/4: gather by the *operand* width
    (B is read at the operand dtype) and waste by the *storage* width
    (padding lanes move value-stream bytes).  work and writeback are
    unchanged — accumulation and output stay f32.
    """
    nnz = max(1, stats["nnz"])
    C = max(1, n_dense_cols)
    row_mean = max(stats["row_mean"], 1e-3)
    row_max = max(stats["row_max"], 1)
    n_rows = max(1, stats["n_rows"])

    work = nnz * C
    if sched.kernel == "rb":
        # ELL pads every row to row_max
        waste = (row_max * n_rows - nnz) * C
        writeback = n_rows * C
    elif sched.is_skew and stats.get("row_quantiles"):
        waste, writeback = _skew_terms(stats, sched, nnz, C, row_mean,
                                       row_max)
    else:
        waste_frac = group_waste_fraction(
            [max(1, int(row_mean))], sched.group_size
        )
        waste = work * waste_frac
        # one writeback per row touched + one carry per group boundary
        groups = nnz / sched.group_size
        rows_touched = nnz / row_mean
        writeback = (rows_touched + groups) * C
    gather = nnz * min(C, sched.col_tile)
    if sched.value_dtype is not None:
        from .dtypes import operand_itemsize, value_itemsize

        waste *= value_itemsize(sched.value_dtype) / 4.0
        gather *= operand_itemsize(sched.value_dtype) / 4.0
    return (float(work), float(waste), float(writeback), float(gather))


def _frac_rows_above(quantiles, thr: float) -> float:
    """Approximate fraction of (non-empty) rows with length > ``thr`` by
    piecewise-linear interpolation of the ``(percent, length)`` quantile
    pairs from ``matrix_stats`` — the cost model's view of the histogram
    the fingerprint hashes."""
    pts = sorted(quantiles)
    if not pts:
        return 0.0
    if thr < pts[0][1]:
        return 1.0
    if thr >= pts[-1][1]:
        # beyond the top quantile: decay the top tail mass linearly
        return max(0.0, (100 - pts[-1][0]) / 100.0 / 2.0)
    for (p0, v0), (p1, v1) in zip(pts, pts[1:]):
        if v0 <= thr < v1:
            t = (thr - v0) / max(1e-9, v1 - v0)
            return 1.0 - (p0 + t * (p1 - p0)) / 100.0
    return 0.0


def _skew_terms(stats: Dict, sched: Schedule, nnz: float, C: float,
                row_mean: float, row_max: float) -> Tuple[float, float]:
    """waste/writeback under the two-level skew layout (DESIGN.md §11):
    the rebalanced histogram the thresholds produce, not the mean-row
    approximation.

    *Heavy* rows (length >= split) sit in dedicated groups padded to the
    group width — at most G-1 pad lanes per row, plus one extra combine
    writeback per heavy group.  *Merged* light rows (length <= merge)
    pack with zero padding.  Mid rows align to a group boundary — on
    average G/2 pad lanes each.
    """
    G = sched.group_size
    rq = stats["row_quantiles"]
    rows_touched = nnz / row_mean
    split = sched.split_threshold or float("inf")
    merge = sched.merge_threshold or 0
    frac_heavy = (0.0 if split == float("inf")
                  else _frac_rows_above(rq, split - 1))
    frac_mid = max(0.0, _frac_rows_above(rq, merge) - frac_heavy)
    heavy_rows = rows_touched * frac_heavy
    mid_rows = rows_touched * frac_mid
    # heavy nnz: mean heavy length approximated by the split/max midpoint
    heavy_nnz = (min(nnz, heavy_rows * (min(split, row_max) + row_max) / 2.0)
                 if heavy_rows > 0 else 0.0)
    waste = (heavy_rows * (G - 1) + mid_rows * G / 2.0) * C
    heavy_groups = (heavy_nnz + heavy_rows * (G - 1)) / G
    tail_groups = max(0.0, nnz - heavy_nnz) / G
    writeback = (rows_touched + heavy_groups + tail_groups) * C
    return float(waste), float(writeback)


def predict_cost(stats: Dict, sched: Schedule, n_dense_cols: int,
                 weights: Sequence[float] | None = None) -> float:
    """Weighted relative cost (lower = better): dot of :func:`cost_terms`
    with ``weights`` (default: the active, possibly calibrated, weights)."""
    w = _cost_weights if weights is None else tuple(weights)
    terms = cost_terms(stats, sched, n_dense_cols)
    return (w[0] * terms[0] + w[1] * terms[1]
            + w[2] * terms[2] + w[3] * terms[3])


#: Relative weight of one wire element vs one local element op in
#: :func:`predict_dist_cost`.  Interconnect bytes are far scarcer than
#: local FLOPs (ICI vs HBM bandwidth), so a wire element costs more than
#: a MAC; like the four local weights this is a ranking prior — the
#: distributed tuner's measurements decide.
WIRE_COST_WEIGHT = 8.0


def collective_cost_terms(collective, *, n_rows: int, n_dense_cols: int,
                          axis_size: int,
                          shard_nnz: "Sequence[int] | None" = None,
                          ) -> Tuple[float, float]:
    """``(wire_elems, imbalance)`` of a collective mode (DESIGN.md §12).

    wire_elems    per-device collective result *elements* — the bytes
                  model ``roofline.analysis.predict_collective_bytes``
                  divided by the itemsize: 'nnz_ar' moves the full
                  ``n_rows * N`` partial, 'nnz_rs' its 1/P row slice,
                  'row' nothing.
    imbalance     max/mean per-shard nnz (>= 1.0): the straggler factor
                  the slowest shard imposes on the whole step.  nnz
                  splits are balanced by construction; 'row' splits
                  inherit the row-block skew via ``shard_nnz``.
    """
    if axis_size <= 1 or collective in (None, "row"):
        wire = 0.0
    else:
        wire = float(n_rows * n_dense_cols)
        if collective == "nnz_rs":
            wire /= axis_size
        elif collective != "nnz_ar":
            raise ValueError(f"unknown collective {collective!r}")
    imbalance = 1.0
    if shard_nnz:
        mean = sum(shard_nnz) / len(shard_nnz)
        if mean > 0:
            imbalance = max(shard_nnz) / mean
    return wire, imbalance


def predict_dist_cost(stats: Dict, sched: Schedule, n_dense_cols: int, *,
                      axis_size: int,
                      shard_nnz: "Sequence[int] | None" = None) -> float:
    """Relative cost of a distributed schedule point: the local cost
    model scaled to the slowest shard, plus the wire term.

    local work is ~1/P of the single-device :func:`predict_cost` times
    the straggler factor; the collective adds ``WIRE_COST_WEIGHT``
    element-costs per wire element.  Used by ``repro.tune``'s
    distributed tuner to rank (tiling × collective) candidates before
    measuring — same role :func:`predict_cost` plays single-device.
    """
    wire, imbalance = collective_cost_terms(
        sched.collective, n_rows=stats["n_rows"],
        n_dense_cols=n_dense_cols, axis_size=axis_size,
        shard_nnz=shard_nnz)
    local = predict_cost(stats, sched, n_dense_cols) / max(axis_size, 1)
    return local * imbalance + WIRE_COST_WEIGHT * wire


def select_schedule(stats: Dict, n_dense_cols: int) -> Schedule:
    """Pick the argmin of the cost model over the candidate grid, with the
    paper's qualitative rules as a prior (they also act as tie-breakers)."""
    cands = candidate_schedules(n_dense_cols)
    best, best_cost = None, math.inf
    for s in cands:
        c = predict_cost(stats, s, n_dense_cols)
        # prior: high row-CV strongly prefers nnz-split + segment
        if stats.get("row_cv", 0.0) > 1.0 and s.kernel == "rb":
            c *= 1.0 + stats["row_cv"]
        if c < best_cost:
            best, best_cost = s, c
    return best
