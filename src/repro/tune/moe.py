"""Empirical tuning of the MoE grouped-matmul dispatch (ISSUE 3).

MoE expert dispatch *is* the paper's DF formulation (sparse routing ⊗
expert GEMM ⊕ segment-sum), so its schedule — token tile, per-expert
capacity, and the GEMM's (f_tile, d_tile) blocking — gets the same
empirical treatment ``tune.search`` gives CSR SpMM:

* the workload fingerprint is the **expert-segment histogram** (how many
  routed tokens each expert received), pushed through the same quantile
  machinery as row lengths (:func:`~.cache.fingerprint_from_lengths`) and
  keyed by ``(n_experts, total routed tokens, histogram quantiles,
  d_model, d_ff, dtype)``;
* the search space is ``token_tile × capacity_factor × f_tile × d_tile``
  with a cost-model warm start, top-k measurement (the static default is
  always in the measured pool, so the tuned point can never lose to it),
  and a ×2 / ÷2 hillclimb — mirroring ``search.tune_schedule``;
* ``capacity_factor`` candidates are **drop-constrained**: a factor that
  would drop more routed tokens than the default does on *this*
  histogram is never offered, so tuning trades time only, never routing
  quality.  Assumed (non-observed) histograms withhold shrinking
  entirely and key a separate cache record (``|ns`` suffix), so the two
  regimes never replay each other's winners;
* winners persist in the same per-backend namespace cache
  (:mod:`~.cache`) under ``moe:``-prefixed keys;
  :func:`moe_cached_or_default` is the measurement-free serving resolver.

The measurement objective is a jitted pure-JAX analogue of
``kernels.grouped_matmul``'s blocking (capacity-gathered tokens →
blocked d→f GEMM → silu → blocked f→d GEMM): XLA compiles a genuinely
different program per (token_tile, f_tile, d_tile, capacity) point, the
same instrument philosophy as ``tune.measure``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import numpy as np

from ..kernels.grouped_matmul import fit_tile as _fit_tile
from ..sparse.formats import round_up as _round_up
from .cache import (
    ScheduleCache,
    default_cache,
    fingerprint_from_lengths,
)
from .driver import TuneResult, _replay, drive
from .measure import time_fn
from .space import CapacityAxis, MoeTilingAxis, SearchContext, SearchSpace

__all__ = [
    "CAPACITY_FACTORS",
    "MoeDispatchSchedule",
    "dropped_tokens",
    "make_moe_runner",
    "measure_moe_dispatch",
    "moe_cache_key",
    "moe_cached_or_default",
    "moe_capacity",
    "moe_cost",
    "moe_schedule_key",
    "tune_moe_dispatch",
]

_TILES = (32, 64, 128, 256)
CAPACITY_FACTORS = (1.0, 1.25, 1.5, 2.0)


@dataclasses.dataclass(frozen=True)
class MoeDispatchSchedule:
    """One point of the MoE dispatch schedule space.

    token_tile       tokens per grid cell of the grouped matmul (each
                     tile belongs to exactly one expert).
    capacity_factor  per-expert capacity multiplier (capacity =
                     mean routed tokens per expert × factor).
    f_tile, d_tile   GEMM blocking of the expert weight (D, F) axes.
    collective       expert-parallel writeback mode (DESIGN.md §12):
                     ``None`` keeps the deployment default ('nnz_ar'),
                     'nnz_ar' all-reduces the partial token block
                     (atomic-style psum), 'nnz_rs' reduce-scatters it so
                     each model shard finalizes a token slice.  'row'
                     has no expert-parallel analogue — every expert's
                     partial covers all local tokens, so a combine is
                     mandatory.
    """

    token_tile: int = 128
    capacity_factor: float = 1.25
    f_tile: int = 128
    d_tile: int = 128
    collective: Optional[str] = None

    def __post_init__(self):
        for name in ("token_tile", "f_tile", "d_tile"):
            v = getattr(self, name)
            if not (isinstance(v, int) and v >= 8):
                raise ValueError(f"{name} must be an int >= 8, got {v!r}")
        if not self.capacity_factor > 0:
            raise ValueError("capacity_factor must be positive, "
                             f"got {self.capacity_factor!r}")
        if self.collective not in (None, "nnz_ar", "nnz_rs"):
            raise ValueError(
                f"unknown collective {self.collective!r}; MoE dispatch "
                "knows 'nnz_ar', 'nnz_rs' (or None for the default)")

    def replace(self, **kw) -> "MoeDispatchSchedule":
        """Copy with the given fields replaced (re-validates)."""
        return dataclasses.replace(self, **kw)


def moe_schedule_key(s: MoeDispatchSchedule) -> str:
    """Stable string identity of a dispatch point (JSON-safe dict key).
    The collective mode is part of the identity — the same GEMM tiling
    under psum and psum_scatter are different SPMD programs."""
    wire = "" if s.collective is None else f":w[{s.collective}]"
    return (f"moe:tt{s.token_tile}:cf{s.capacity_factor:g}"
            f":f{s.f_tile}:d{s.d_tile}{wire}")


def moe_cache_key(expert_lengths, d_model: int, d_ff: int,
                  dtype: str = "float32", *, shrink: bool = True,
                  max_tokens: Optional[int] = None) -> str:
    """Cache key of a dispatch workload: the expert-segment histogram
    fingerprint (n_experts × total routed tokens × quantiles × CV) plus
    the GEMM dims and dtype.  Backend lives in the cache namespace, not
    the key.  ``shrink=False`` (assumed-histogram tuning, where capacity
    shrinking is withheld) keys a *separate* record: the two regimes
    search different spaces, so a shrunk winner cached from observed
    routing must never replay for an assumed-histogram caller (and an
    assumed no-shrink winner must not block an observed tune).
    ``max_tokens`` — the deployed capacity clamp — is part of the key
    too: identical histograms under different token budgets measure
    different programs and must not share a record."""
    lengths = np.asarray(expert_lengths)
    fp = fingerprint_from_lengths(lengths, (int(lengths.shape[0]), d_model),
                                  int(lengths.sum()))
    tok = f"|T{int(max_tokens)}" if max_tokens is not None else ""
    ns = "" if shrink else "|ns"
    return f"moe:{fp}|F{int(d_ff)}|{dtype}{tok}{ns}"


# ---------------------------------------------------------------------------
# Capacity / cost model
# ---------------------------------------------------------------------------


def moe_capacity(expert_lengths, capacity_factor: float, *,
                 max_tokens: Optional[int] = None) -> int:
    """Per-expert capacity implied by a factor on this histogram: mean
    routed tokens per expert × factor, floored at 8 (mirrors
    ``models.moe._capacity``).  ``max_tokens`` is the deployed upper
    clamp — the local token count ``_capacity`` caps at; without it the
    total routed-assignment count stands in (a looser bound that only
    differs when ``experts_per_token × factor > n_experts``)."""
    lengths = np.asarray(expert_lengths, np.float64)
    e = max(int(lengths.shape[0]), 1)
    cap = int(float(lengths.sum()) * capacity_factor / e)
    upper = int(max_tokens) if max_tokens is not None else int(lengths.sum())
    return min(max(8, cap), max(upper, 8))


def dropped_tokens(expert_lengths, capacity: int) -> int:
    """Routed tokens that do not fit their expert's capacity (the
    routing-quality price of a small capacity factor)."""
    lengths = np.asarray(expert_lengths, np.int64)
    return int(np.maximum(lengths - capacity, 0).sum())




def _token_tiling(capacity: int, token_tile: int) -> tuple:
    """``(tile, cap_pad)`` exactly as the deployed dispatch computes it
    (``models.moe._expert_ffn``): the tile is clamped to the capacity and
    the capacity is padded *up* to the tile — so the cost prior and the
    measurement objective see the padding a deployed tile choice pays."""
    tile = min(max(capacity, 8), token_tile)
    return tile, _round_up(max(capacity, 8), tile)


def _effective_program(expert_lengths, s: MoeDispatchSchedule,
                       d_model: int, d_ff: int,
                       max_tokens: Optional[int] = None) -> tuple:
    """The compiled shape a schedule actually produces: ``(tile,
    cap_pad, d_tile, f_tile)`` after capacity and tile fitting.  Several
    nominal grid points collapse to one program (e.g. d_tile 128 and 256
    both fit to 128 when d_model=128) — the search dedupes on this so
    timing noise never arbitrates between byte-identical programs."""
    cap = moe_capacity(expert_lengths, s.capacity_factor,
                       max_tokens=max_tokens)
    tile, cap_pad = _token_tiling(cap, s.token_tile)
    return (tile, cap_pad, _fit_tile(int(d_model), s.d_tile),
            _fit_tile(int(d_ff), s.f_tile))


def moe_cost(expert_lengths, s: MoeDispatchSchedule, d_model: int,
             d_ff: int, max_tokens: Optional[int] = None) -> float:
    """Static cost prior over the dispatch space (warm start only —
    measurement decides).  Terms: useful + padding flops of the
    capacity-padded grouped GEMM, tile-granularity memory traffic
    (smaller tiles re-fetch weight blocks more often), and a per-program
    launch overhead."""
    lengths = np.asarray(expert_lengths, np.float64)
    e = max(int(lengths.shape[0]), 1)
    d, f = int(d_model), int(d_ff)
    cap = moe_capacity(lengths, s.capacity_factor, max_tokens=max_tokens)
    tt, cap_pad = _token_tiling(cap, s.token_tile)
    dt, ft = _fit_tile(d, s.d_tile), _fit_tile(f, s.f_tile)

    occupied = float(np.minimum(lengths, cap).sum())
    work = occupied * d * f
    waste = (e * cap_pad - occupied) * d * f
    grid = (e * cap_pad // tt) * (f // ft) * (d // dt)
    traffic = grid * (tt * dt + dt * ft + tt * ft)
    return work + waste + 8.0 * traffic + 500.0 * grid


def candidate_moe_schedules(
        expert_lengths, *,
        default: Optional[MoeDispatchSchedule] = None,
        allow_capacity_shrink: bool = True,
        max_tokens: Optional[int] = None,
) -> List[MoeDispatchSchedule]:
    """The tuning grid.  Capacity factors that would drop more routed
    tokens than the default factor does on this histogram are excluded
    (time-for-quality trades are not the tuner's to make).  When the
    histogram is *assumed* rather than observed, pass
    ``allow_capacity_shrink=False``: the drop constraint is only
    trustworthy on real routing counts, so sub-default factors — safe on
    the assumed histogram, token-dropping on a skewed live batch — are
    withheld entirely."""
    default = default or MoeDispatchSchedule()
    budget = dropped_tokens(
        expert_lengths, moe_capacity(expert_lengths,
                                     default.capacity_factor,
                                     max_tokens=max_tokens))
    factors = sorted({default.capacity_factor} | {
        cf for cf in CAPACITY_FACTORS
        if cf >= default.capacity_factor or (
            allow_capacity_shrink
            and dropped_tokens(
                expert_lengths,
                moe_capacity(expert_lengths, cf,
                             max_tokens=max_tokens)) <= budget)})
    return [MoeDispatchSchedule(token_tile=tt, capacity_factor=cf,
                                f_tile=ft, d_tile=dt)
            for cf in factors
            for tt in _TILES
            for ft in _TILES
            for dt in _TILES]


# ---------------------------------------------------------------------------
# Measurement: jitted blocked-GEMM analogue of kernels.grouped_matmul
# ---------------------------------------------------------------------------


def make_moe_runner(expert_lengths, d_model: int, d_ff: int,
                    s: MoeDispatchSchedule, dtype: str = "float32",
                    max_tokens: Optional[int] = None):
    """Build ``(fn, args)`` timing one dispatch pass: capacity-gathered
    tokens through a blocked d→f GEMM, silu, and a blocked f→d GEMM,
    with the expert weight selected per token tile — the pure-JAX
    analogue of the Pallas kernel's grid."""
    import jax
    import jax.numpy as jnp

    lengths = np.asarray(expert_lengths)
    e = max(int(lengths.shape[0]), 1)
    d, f = int(d_model), int(d_ff)
    cap = moe_capacity(lengths, s.capacity_factor, max_tokens=max_tokens)
    tt, cap_pad = _token_tiling(cap, s.token_tile)
    dt, ft = _fit_tile(d, s.d_tile), _fit_tile(f, s.f_tile)
    n_tiles = e * cap_pad // tt
    tile_experts = np.repeat(np.arange(e, dtype=np.int32), cap_pad // tt)

    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (e * cap_pad, d), dtype=jnp.float32)
    w1 = jax.random.normal(k2, (e, d, f), dtype=jnp.float32)
    w2 = jax.random.normal(k3, (e, f, d), dtype=jnp.float32)
    x, w1, w2 = (a.astype(dtype) for a in (x, w1, w2))
    emap = jnp.asarray(tile_experts)

    def _run(x, w1, w2):
        xt = x.reshape(n_tiles, tt, d // dt, dt)
        w1t = w1[emap].reshape(n_tiles, d // dt, dt, f // ft, ft)
        h = jnp.einsum("ntkc,nkcmf->ntmf", xt, w1t,
                       preferred_element_type=jnp.float32)
        h = jax.nn.silu(h).astype(x.dtype)  # (n_tiles, tt, f//ft, ft)
        w2t = w2[emap].reshape(n_tiles, f // ft, ft, d // dt, dt)
        y = jnp.einsum("ntmc,nmckd->ntkd", h, w2t,
                       preferred_element_type=jnp.float32)
        return y.reshape(e * cap_pad, d)

    return jax.jit(_run), (x, w1, w2)


def measure_moe_dispatch(expert_lengths, d_model: int, d_ff: int,
                         s: MoeDispatchSchedule, *, dtype: str = "float32",
                         warmup: Optional[int] = None,
                         iters: Optional[int] = None,
                         max_tokens: Optional[int] = None) -> float:
    """Seconds/call of one dispatch pass under schedule ``s`` — the MoE
    tuner's objective function."""
    fn, args = make_moe_runner(expert_lengths, d_model, d_ff, s, dtype,
                               max_tokens)
    return time_fn(fn, *args, warmup=warmup, iters=iters)


# ---------------------------------------------------------------------------
# The tuner
# ---------------------------------------------------------------------------


def tune_moe_dispatch(
    expert_lengths,
    d_model: int,
    d_ff: int,
    *,
    dtype: str = "float32",
    default: Optional[MoeDispatchSchedule] = None,
    cache: Optional[ScheduleCache] = None,
    top_k: int = 4,
    hill_steps: int = 3,
    measure: Optional[Callable[[MoeDispatchSchedule], float]] = None,
    warmup: Optional[int] = None,
    iters: Optional[int] = None,
    backend: Optional[str] = None,
    allow_capacity_shrink: bool = True,
    max_tokens: Optional[int] = None,
) -> TuneResult:
    """Empirically pick the dispatch schedule for this expert histogram;
    same phases as :func:`~.search.tune_schedule` (cache replay → cost
    warm start → top-k measurement with the static default always in the
    pool → hillclimb → persist).

    expert_lengths  routed tokens per expert (the segment histogram);
    d_model / d_ff  GEMM dims of the expert FFN;
    default         the static point tuning must never lose to
                    (``MoeDispatchSchedule()`` with the config's
                    capacity factor, normally);
    measure         override objective ``schedule -> seconds`` (tests);
    allow_capacity_shrink
                    pass False when ``expert_lengths`` is assumed, not
                    observed (see :func:`candidate_moe_schedules`); the
                    flag is part of the cache key, so the two regimes
                    never replay each other's records;
    max_tokens      the deployed local token count (deployment clamps
                    capacity at it — see :func:`moe_capacity`).
    """
    if cache is None:
        cache = default_cache(backend)
    default = default or MoeDispatchSchedule()
    key = moe_cache_key(expert_lengths, d_model, d_ff, dtype,
                        shrink=allow_capacity_shrink,
                        max_tokens=max_tokens)
    hit = _replay(cache, key)
    if hit is not None:
        return hit

    if measure is None:
        def measure(s: MoeDispatchSchedule) -> float:
            return measure_moe_dispatch(expert_lengths, d_model, d_ff, s,
                                        dtype=dtype, warmup=warmup,
                                        iters=iters, max_tokens=max_tokens)

    cands = candidate_moe_schedules(
        expert_lengths, default=default,
        allow_capacity_shrink=allow_capacity_shrink, max_tokens=max_tokens)
    factors = sorted({c.capacity_factor for c in cands})
    ranked = sorted(cands, key=lambda s: moe_cost(expert_lengths, s,
                                                  d_model, d_ff, max_tokens))

    def _eff(s: MoeDispatchSchedule) -> tuple:
        return _effective_program(expert_lengths, s, d_model, d_ff,
                                  max_tokens)

    # the dispatch space dedupes on the *effective* program: nominal
    # points that fit to the same (tile, cap_pad, dt, ft) compile
    # identically, so measuring two of them would let timing noise pick
    # a "winner"
    space = SearchSpace(
        (MoeTilingAxis(_TILES), CapacityAxis(factors)),
        key_fn=moe_schedule_key,
        dedupe=lambda c, s: _eff(s),
    )
    return drive(space, SearchContext(workload=expert_lengths),
                 cache=cache, key=key, measure=measure,
                 seeds=[default], ranked=ranked, top_k=top_k,
                 hill_steps=hill_steps)


def moe_cached_or_default(
        expert_lengths, d_model: int, d_ff: int, *,
        dtype: str = "float32",
        default: Optional[MoeDispatchSchedule] = None,
        cache: Optional[ScheduleCache] = None,
        backend: Optional[str] = None,
        allow_capacity_shrink: bool = True,
        max_tokens: Optional[int] = None,
) -> MoeDispatchSchedule:
    """Cache-hit dispatch schedule if one exists, else the static
    default — **never measures** (the serving-path resolver; tune ahead
    of time with :func:`tune_moe_dispatch`, e.g. through
    ``ServeEngine.prepare_moe``).  ``allow_capacity_shrink`` and
    ``max_tokens`` must match the tuning call — they select which
    record to replay."""
    if cache is None:
        cache = default_cache(backend)
    rec = cache.get(moe_cache_key(expert_lengths, d_model, d_ff, dtype,
                                  shrink=allow_capacity_shrink,
                                  max_tokens=max_tokens))
    if rec is not None and isinstance(rec.schedule, MoeDispatchSchedule):
        return rec.schedule
    return default or MoeDispatchSchedule()
