"""Fused-sparse-attention schedule tuning (DESIGN.md §9).

The fused attention kernels expose the same (nnz_tile, group_size,
strategy) axes as ``segment_reduce`` — but the *objective* differs per
direction: the forward is a (2, nnz_tiles) grid (a row-max pass, then
a weighted-sum pass), the backward one pass with a row scatter and a
column scatter.  A schedule tuned for one is not evidence
about the other, and batching H heads into one launch changes the
arithmetic intensity per pattern byte.  The cache key therefore carries
the **direction** (``fwd``/``bwd``), the **head count**, the feature
widths and the bias-operand flag alongside the row-histogram
fingerprint — a fwd record never replays for a bwd query, nor an H=1
record for an H=8 one.

Like ``tune_segment_reduce``, the objective times the *actual* Pallas
kernels (there is no cheaper analogue that still observes the tile
axis); 'parallel' is excluded from the pool (``sparse_attention``
rejects it).
"""
from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from ..core import Schedule
from .cache import ScheduleCache, default_cache, fingerprint_from_lengths
from .driver import TuneResult, _replay, drive
from .measure import time_fn
from .space import (SearchContext, SearchSpace, StrategyAxis, TilingAxis,
                    schedule_key)

__all__ = [
    "attention_cache_key",
    "tune_sparse_attention",
]

#: (nnz_tile, group_size, strategy) pool measured per pattern — the EB
#: half of the grid minus 'parallel' (rejected for attention rows).
_POOL = [Schedule("eb", nnz_tile=tile, group_size=g, strategy=st)
         for tile in (128, 512)
         for g in (8, 32)
         for st in ("segment", "accumulate")]


def attention_cache_key(rows, n_rows: int, *, n_cols: int, d: int,
                        dv: int, n_heads: int, direction: str,
                        has_bias: bool = False) -> str:
    """Cache key for a fused-attention tuning record.

    Distinguishes forward from backward and the head count (plus the
    feature widths and whether a bias operand rides along): the two
    directions run different grids with different traffic patterns, so
    their winners must never alias.  ``n_cols`` (the key/value count) is
    part of the fingerprint shape — the kernel holds (n_kv, ·) resident
    blocks, so patterns differing only in n_kv must not share records.
    """
    if direction not in ("fwd", "bwd"):
        raise ValueError(f"direction must be 'fwd' or 'bwd', "
                         f"got {direction!r}")
    rows_np = np.asarray(rows)
    lengths = np.bincount(rows_np, minlength=max(n_rows, 1))
    fp = fingerprint_from_lengths(lengths, (n_rows, n_cols),
                                  rows_np.shape[0])
    b = "|b" if has_bias else ""
    return f"attn:{fp}|d{d}|dv{dv}|H{n_heads}|{direction}{b}"


def tune_sparse_attention(
    rows,
    cols,
    q,
    k,
    v,
    *,
    n_rows: int,
    bias=None,
    scale: Optional[float] = None,
    direction: str = "fwd",
    cache: Optional[ScheduleCache] = None,
    measure: Optional[Callable[[Schedule], float]] = None,
    warmup: Optional[int] = None,
    iters: Optional[int] = None,
    backend: Optional[str] = None,
) -> TuneResult:
    """Empirically pick (nnz_tile, group_size, strategy) for the fused
    sparse-attention kernel over this pattern.

    ``direction='fwd'`` times :func:`~repro.kernels.fused_attention.
    fused_sparse_attention`; ``'bwd'`` times the fused backward (running
    one forward per candidate first to obtain the (m, l) residuals the
    backward consumes).  q/k/v may be 2-D (single head) or (n, H, ·) —
    the head count is part of the cache key.  A cache hit replays with
    zero measurements."""
    import jax
    import jax.numpy as jnp

    from ..kernels.fused_attention import (
        fused_sparse_attention,
        fused_sparse_attention_bwd,
    )
    from ..sparse.formats import round_up
    from ..sparse.ops import _attn_heads

    qh, kh, vh, _ = _attn_heads(q, k, v)
    _, n_heads, d = qh.shape
    n_cols, dv = vh.shape[0], vh.shape[-1]
    if scale is None:
        scale = float(d) ** -0.5
    key = attention_cache_key(rows, n_rows, n_cols=n_cols, d=d, dv=dv,
                              n_heads=n_heads, direction=direction,
                              has_bias=bias is not None)
    if cache is None:
        cache = default_cache(backend)
    hit = _replay(cache, key)
    if hit is not None:
        return hit

    if measure is None:
        nnz = int(np.asarray(rows).shape[0])
        # the cotangent has the OUTPUT's shape — (n_rows, H, dv), not
        # v's (n_cols, H, dv); they only coincide on square patterns
        dout = jax.random.normal(jax.random.PRNGKey(0),
                                 (n_rows, n_heads, dv))

        def measure(s: Schedule) -> float:
            nnz_pad = max(round_up(max(nnz, 1), s.nnz_tile), s.nnz_tile)
            pad = nnz_pad - nnz
            rows_p = jnp.pad(jnp.asarray(rows), (0, pad))
            cols_p = jnp.pad(jnp.asarray(cols), (0, pad))
            bias_p = (None if bias is None
                      else jnp.pad(bias.astype(jnp.float32), (0, pad)))

            def fwd(qq, kk, vv):
                return fused_sparse_attention(
                    rows_p, cols_p, qq, kk, vv, n_rows=n_rows, nnz=nnz,
                    nnz_tile=s.nnz_tile, scale=scale,
                    group_size=s.group_size, strategy=s.strategy,
                    bias=bias_p)

            if direction == "fwd":
                return time_fn(lambda qq, kk, vv: fwd(qq, kk, vv)[0],
                               qh, kh, vh, warmup=warmup, iters=iters)
            out, m, l = fwd(qh, kh, vh)

            def bwd(qq, kk, vv, do):
                return fused_sparse_attention_bwd(
                    rows_p, cols_p, qq, kk, vv, out, do, m, l, n_rows=n_rows,
                    nnz=nnz, nnz_tile=s.nnz_tile, scale=scale,
                    group_size=s.group_size, strategy=s.strategy,
                    bias=bias_p)

            return time_fn(bwd, qh, kh, vh, dout,
                           warmup=warmup, iters=iters)

    # exhaustive over the fixed pool: the driver measures every ranked
    # point (top_k=None) and skips hillclimb/variant stages
    space = SearchSpace((StrategyAxis(), TilingAxis()), key_fn=schedule_key)
    return drive(space, SearchContext(), cache=cache, key=key,
                 measure=measure, ranked=_POOL)
