"""Sparse matrix storage formats as JAX pytrees.

Formats
-------
COO              (rows, cols, vals) unsorted triplets — interchange format.
CSR              classic compressed-sparse-row — canonical logical format.
GroupedCOO       row-sorted COO padded to a multiple of ``nnz_tile`` — the
                 feed format of the nnz-split (EB) segment-group kernel.
                 Padding uses ``val = 0`` so padded lanes are *zero
                 extension* in the paper's sense: they flow through the
                 vector/MXU datapath and contribute nothing.
ELL              per-row padded (blocked-ELL when viewed in row tiles) —
                 the feed format of the row-split (RB) kernel.
BlockedCOO       GroupedCOO's lanes partitioned into 2-D blocks of (row
                 window, column window) — the feed format of the windowed
                 EB launch, for operands larger than VMEM.

All formats carry their dense ``shape`` and padding parameters as static
metadata so they can cross ``jit`` boundaries.

``CSR`` memoizes its kernel-feed conversions per ``(format, tile)`` —
``csr.grouped(nnz_tile)`` / ``csr.ell(row_tile)`` / ``csr.tocoo()`` — so
training loops that call ``spmm`` on the same matrix every step don't
re-convert.  The cache only engages on concrete (non-traced) arrays; it is
deliberately not part of the pytree, so transformed copies start cold.

Skew-partitioned grouping (DESIGN.md §11): ``grouped`` / ``regrouped``
accept ``group_size=`` plus ``split_threshold=`` / ``merge_threshold=``
and emit a *two-level* layout for power-law matrices — heavy rows are
split across dedicated width-G groups up front (combined across groups
by the registry's accumulate-style read-modify-write), light rows are
merged into shared groups behind them.  The layout is carried in the
static ``skew`` metadata; each parameter combination is its own memo
key, so a tuner sweeping thresholds converts each layout once.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["COO", "CSR", "GroupedCOO", "BlockedCOO", "ELL", "QuantizedCSR",
           "quantize_csr", "dequantize", "round_up"]


def round_up(x: int, m: int) -> int:
    """Smallest multiple of ``m`` that is >= ``x`` (tile padding)."""
    return ((x + m - 1) // m) * m


def _instance_cache(obj, arrays):
    """Per-instance conversion memo, or None while being traced (caching
    tracers would leak them across jit traces).  Deliberately not part of
    the pytree: transformed copies start cold."""
    if any(isinstance(x, jax.core.Tracer) for x in arrays):
        return None
    cache = obj.__dict__.get("_convcache")
    if cache is None:
        cache = {}
        object.__setattr__(obj, "_convcache", cache)
    return cache


def _memoized(obj, arrays, key, build):
    cache = _instance_cache(obj, arrays)
    if cache is None:
        return build()
    if key not in cache:
        cache[key] = build()
    return cache[key]


def _csr_scatter_index(indptr):
    """(row_ids, positions) int arrays: nnz t of CSR row r lands in ELL
    slot ``t - indptr[r]``.  Shared by ``ELL.fromcsr`` and
    ``CSR.ell_scatter_index``."""
    indptr = np.asarray(indptr).astype(np.int64)
    lengths = indptr[1:] - indptr[:-1]
    row_ids = np.repeat(np.arange(lengths.shape[0]), lengths)
    pos = np.arange(indptr[-1]) - np.repeat(indptr[:-1], lengths)
    return row_ids, pos


def _concrete_np(x, what: str):
    """``np.asarray(x)`` with a readable error under a jit tracer — the
    host-side skew layout pass needs concrete index arrays."""
    if isinstance(x, jax.core.Tracer):
        raise TypeError(
            f"{what} requires concrete (non-traced) arrays: the two-level "
            "skew layout is a host-side format pass — build the grouped "
            "format outside jit (it is memoized, so once is enough)")
    return np.asarray(x)


def _skew_layout(indptr, indices, shape, nnz_tile: int,
                 group_size: int, split_threshold: int | None,
                 merge_threshold: int | None):
    """Host-side two-level layout pass (DESIGN.md §11).

    Returns ``(rows, cols, positions, heavy_tiles)`` numpy arrays:
    a padded COO stream whose first ``heavy_tiles`` nnz tiles hold the
    *heavy* rows (``length >= split_threshold``), each split across
    dedicated width-``group_size`` groups padded with the row's own id —
    so every heavy group is single-row and reduces with the registry's
    'parallel' realization, cross-group partials combining through its
    accumulate-style read-modify-write.  The remaining tiles hold the
    tail: rows in row order, runs of light rows (``length <=
    merge_threshold``) merged into shared groups, longer tail rows
    aligned to a group boundary (padding the gap with the previous row's
    id, val 0 — zero extension).  ``positions[t]`` is the padded slot of
    original CSR lane ``t`` — values (which may be jit tracers) are
    scattered through it by the caller, so only the *index* arrays need
    to be concrete here.
    """
    assert nnz_tile % group_size == 0, (nnz_tile, group_size)
    indptr = np.asarray(indptr).astype(np.int64)
    indices = np.asarray(indices)
    n_rows = shape[0]
    lengths = indptr[1:] - indptr[:-1]
    pad_row = n_rows - 1
    G = group_size
    S = np.iinfo(np.int64).max if split_threshold is None else split_threshold
    M = np.iinfo(np.int64).max if merge_threshold is None else merge_threshold

    heavy = lengths >= S
    h_ids = np.nonzero(heavy)[0]
    h_lens = lengths[h_ids]
    h_pad = -(-h_lens // G) * G  # per-row round up to the group width
    h_starts = np.concatenate([[0], np.cumsum(h_pad)])[:-1]
    heavy_total = int(h_pad.sum())
    heavy_region = round_up(heavy_total, nnz_tile) if heavy_total else 0

    t_ids = np.nonzero(~heavy & (lengths > 0))[0]
    t_starts = np.empty(len(t_ids), np.int64)
    gaps = []  # (offset, pad lanes, filler row id) alignment gaps
    off = 0
    prev_row = 0
    for i, r in enumerate(t_ids):
        length = int(lengths[r])
        if length > M and off % G:
            pad = G - off % G
            gaps.append((off, pad, prev_row))
            off += pad
        t_starts[i] = off
        off += length
        prev_row = int(r)
    tail_region = round_up(off, nnz_tile) if off else 0

    total = heavy_region + tail_region
    if total == 0:
        total = nnz_tile  # empty matrix: one all-pad tile (as fromcsr)
    rows = np.full(total, pad_row, np.int32)
    cols = np.zeros(total, np.int32)

    starts = np.zeros(n_rows, np.int64)
    starts[h_ids] = h_starts
    starts[t_ids] = heavy_region + t_starts
    row_ids, pos = _csr_scatter_index(indptr)
    positions = (starts[row_ids] + pos).astype(np.int64)
    rows[positions] = row_ids
    cols[positions] = indices
    # heavy per-row padding keeps the row's own id: every heavy group is
    # single-row, so 'parallel' may reduce it with one writeback
    spans = h_pad - h_lens
    if spans.sum():
        base = np.repeat(h_starts + h_lens, spans)
        local = np.arange(int(spans.sum())) - np.repeat(
            np.concatenate([[0], np.cumsum(spans)])[:-1], spans)
        rows[base + local] = np.repeat(h_ids, spans)
    for g_off, g_pad, filler in gaps:
        rows[heavy_region + g_off: heavy_region + g_off + g_pad] = filler

    return (rows, cols, positions.astype(np.int32),
            heavy_region // nnz_tile)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["rows", "cols", "vals"],
    meta_fields=["shape"],
)
@dataclasses.dataclass(frozen=True)
class COO:
    """Unordered triplet format. ``shape`` is the dense (n_rows, n_cols)."""

    rows: jax.Array  # (nnz,) int32
    cols: jax.Array  # (nnz,) int32
    vals: jax.Array  # (nnz,) float
    shape: tuple

    @property
    def nnz(self) -> int:
        """Stored-triplet count."""
        return self.vals.shape[0]

    def todense(self) -> jax.Array:
        """Scatter-add the triplets into a dense ``shape`` array."""
        out = jnp.zeros(self.shape, self.vals.dtype)
        return out.at[self.rows, self.cols].add(self.vals)

    @staticmethod
    def fromdense(mat) -> "COO":
        """Dense array -> row-major-sorted COO of its nonzeros."""
        mat = np.asarray(mat)
        rows, cols = np.nonzero(mat)
        order = np.lexsort((cols, rows))
        return COO(
            rows=jnp.asarray(rows[order], jnp.int32),
            cols=jnp.asarray(cols[order], jnp.int32),
            vals=jnp.asarray(mat[rows[order], cols[order]]),
            shape=mat.shape,
        )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["indptr", "indices", "vals"],
    meta_fields=["shape"],
)
@dataclasses.dataclass(frozen=True)
class CSR:
    """Compressed sparse row — the canonical input format.  Conversions
    (``tocoo``/``grouped``/``ell``) are memoized per instance, so a
    serving loop converts once however many calls reuse the matrix."""

    indptr: jax.Array  # (n_rows + 1,) int32
    indices: jax.Array  # (nnz,) int32 column ids
    vals: jax.Array  # (nnz,)
    shape: tuple

    @property
    def nnz(self) -> int:
        """Stored-value count."""
        return self.vals.shape[0]

    def row_lengths(self) -> jax.Array:
        """(n_rows,) per-row nnz counts — the histogram the fingerprint
        and the skew thresholds are derived from."""
        return self.indptr[1:] - self.indptr[:-1]

    # -- conversion caching ------------------------------------------------

    def _cached(self, key, build):
        return _memoized(self, (self.indptr, self.indices, self.vals),
                         key, build)

    def tocoo(self) -> "COO":
        """Memoized CSR -> COO expansion (format-time searchsorted
        replaces the paper's per-thread taco_binarySearchBefore)."""
        def _build():
            rows = jnp.searchsorted(
                self.indptr, jnp.arange(self.nnz, dtype=jnp.int32),
                side="right",
            ).astype(jnp.int32) - 1
            return COO(rows=rows, cols=self.indices, vals=self.vals,
                       shape=self.shape)

        return self._cached("coo", _build)

    def grouped(self, nnz_tile: int, *, group_size: int | None = None,
                split_threshold: int | None = None,
                merge_threshold: int | None = None) -> "GroupedCOO":
        """EB-kernel feed format, memoized per parameter tuple.

        With ``split_threshold`` / ``merge_threshold`` set (and the
        schedule's ``group_size``), the conversion runs the two-level
        skew layout (:func:`_skew_layout`): heavy rows split across
        dedicated groups up front, light rows merged into shared groups
        behind.  Each distinct ``(nnz_tile, group_size, split, merge)``
        is its own cache entry, so a tuner sweeping thresholds converts
        each layout exactly once per matrix.
        """
        if split_threshold is None and merge_threshold is None:
            return self._cached(("grouped", nnz_tile),
                                lambda: GroupedCOO.fromcsr(self, nnz_tile))
        key = ("grouped", nnz_tile, group_size, split_threshold,
               merge_threshold)
        return self._cached(
            key, lambda: GroupedCOO.fromcsr(
                self, nnz_tile, group_size=group_size,
                split_threshold=split_threshold,
                merge_threshold=merge_threshold))

    def blocked(self, nnz_tile: int, windows: tuple) -> "BlockedCOO":
        """Windowed EB-kernel feed format, memoized per ``(nnz_tile,
        windows)``; a host pass over concrete indices."""
        def _build():
            indptr = _concrete_np(self.indptr, "the blocked layout")
            rows = np.repeat(np.arange(self.shape[0]), np.diff(indptr))
            return BlockedCOO.fromcoo(
                rows, _concrete_np(self.indices, "the blocked layout"),
                self.vals, self.shape, nnz_tile, windows)

        return self._cached(("blocked", nnz_tile, tuple(windows)), _build)

    def ell(self, row_tile: int = 8, width: int | None = None) -> "ELL":
        """RB-kernel feed format, memoized per (row_tile, width)."""
        return self._cached(("ell", row_tile, width),
                            lambda: ELL.fromcsr(self, width=width,
                                                row_tile=row_tile))

    def ell_scatter_index(self):
        """(row_ids, positions) int32 arrays scattering the flat CSR value
        stream into the ELL (row, slot) layout — lets callers rebuild
        ``ELL.vals`` from fresh values (e.g. inside autodiff) without a
        Python loop.  Requires concrete arrays."""
        def _build():
            row_ids, pos = _csr_scatter_index(self.indptr)
            return (jnp.asarray(row_ids, jnp.int32),
                    jnp.asarray(pos, jnp.int32))

        return self._cached("ell_scatter", _build)

    def astype(self, dtype) -> "CSR":
        """This matrix with values stored in ``dtype``, memoized per
        target (DESIGN.md §13).

        Returns ``self`` when the dtype already matches.  Memoization
        makes the cast instance *stable*, so its own conversion memos
        (``grouped``/``ell``) warm up exactly once per (matrix, dtype) —
        a serving loop running a ``value_dtype`` schedule pays the cast
        and re-grouping on the first call only.
        """
        dt = np.dtype(dtype)
        if dt == self.vals.dtype:
            return self
        return self._cached(
            ("astype", str(dt)),
            lambda: CSR(indptr=self.indptr, indices=self.indices,
                        vals=self.vals.astype(dt), shape=self.shape))

    def quantized(self, *, method: str = "absmax",
                  percentile: float = 99.9) -> "QuantizedCSR":
        """Memoized int8 quantization of this matrix — see
        :func:`quantize_csr` (host-side pass; requires concrete
        arrays)."""
        return self._cached(
            ("quantized", method, percentile),
            lambda: quantize_csr(self, method=method,
                                 percentile=percentile))

    def todense(self) -> jax.Array:
        """Dense (n_rows, n_cols) array of this matrix."""
        return self.tocoo().todense()

    @staticmethod
    def fromdense(mat) -> "CSR":
        """Dense array -> CSR of its nonzeros (host-side numpy pass)."""
        mat = np.asarray(mat)
        # np.nonzero is C-ordered: already sorted by (row, col).
        rows, cols = np.nonzero(mat)
        counts = np.bincount(rows, minlength=mat.shape[0])
        indptr = np.concatenate([[0], np.cumsum(counts)])
        return CSR(
            indptr=jnp.asarray(indptr, jnp.int32),
            indices=jnp.asarray(cols, jnp.int32),
            vals=jnp.asarray(mat[rows, cols]),
            shape=mat.shape,
        )

    @staticmethod
    def fromcoo(coo: COO) -> "CSR":
        """COO (any order) -> row-major CSR (host-side numpy sort)."""
        rows = np.asarray(coo.rows)
        cols = np.asarray(coo.cols)
        vals = np.asarray(coo.vals)
        order = np.lexsort((cols, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        counts = np.bincount(rows, minlength=coo.shape[0])
        indptr = np.concatenate([[0], np.cumsum(counts)])
        return CSR(
            indptr=jnp.asarray(indptr, jnp.int32),
            indices=jnp.asarray(cols, jnp.int32),
            vals=jnp.asarray(vals),
            shape=coo.shape,
        )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["rows", "cols", "vals"],
    meta_fields=["shape", "nnz", "nnz_tile", "skew"],
)
@dataclasses.dataclass(frozen=True)
class GroupedCOO:
    """Row-sorted COO padded to a multiple of ``nnz_tile``.

    Feed format for the nnz-split segment-group kernel: a grid cell owns one
    ``nnz_tile`` slice; ``rows`` is the precomputed per-nnz row-id stream.
    Padded lanes have ``val == 0`` (zero extension — they reduce into a
    live row but contribute nothing); trailing padding targets row
    ``shape[0] - 1``.

    ``skew`` is ``None`` for the standard trailing-padded layout, or the
    static tuple ``(split_threshold, merge_threshold, group_size,
    heavy_tiles)`` for the two-level layout (:func:`_skew_layout`): the
    first ``heavy_tiles`` nnz tiles hold split heavy rows (single-row
    groups), the rest the merged tail.  Skew layouts interleave padding
    with data, so value updates must go through :meth:`skew_positions`
    rather than slicing ``vals[:nnz]``.
    """

    rows: jax.Array  # (nnz_padded,) int32, non-decreasing
    cols: jax.Array  # (nnz_padded,) int32
    vals: jax.Array  # (nnz_padded,)
    shape: tuple
    nnz: int  # true nnz (static)
    nnz_tile: int
    skew: "tuple | None" = None

    @property
    def nnz_padded(self) -> int:
        """Total lane count including padding (a ``nnz_tile`` multiple)."""
        return self.vals.shape[0]

    @property
    def num_tiles(self) -> int:
        """Grid extent along the nnz axis: ``nnz_padded / nnz_tile``."""
        return self.nnz_padded // self.nnz_tile

    @property
    def heavy_tiles(self) -> int:
        """Leading nnz tiles holding split heavy rows (0 for the standard
        layout) — the EB kernel runs these under the 'parallel'
        realization regardless of the schedule's tail strategy."""
        return self.skew[3] if self.skew is not None else 0

    def skew_positions(self) -> jax.Array:
        """(nnz,) int32 scatter index: padded slot of original CSR lane t.

        Only skew layouts carry one (standard layouts are trailing-padded,
        so ``[:nnz]`` slicing suffices); it lets autodiff rebuild
        ``vals`` from a fresh value stream without re-running the layout
        pass.  Lost on pytree-transformed copies — rebuild the format
        from its source CSR in that case."""
        pos = self.__dict__.get("_skew_positions")
        if pos is None:
            raise ValueError(
                "this GroupedCOO carries no skew scatter index (standard "
                "layout, or a transformed copy); rebuild it via "
                "CSR.grouped(..., split_threshold=...)")
        return pos

    @staticmethod
    def fromcsr(csr: CSR, nnz_tile: int, *, group_size: int | None = None,
                split_threshold: int | None = None,
                merge_threshold: int | None = None) -> "GroupedCOO":
        """Convert a CSR; thresholds select the two-level skew layout.

        The skew path is a host-side numpy pass over concrete index
        arrays (it raises under jit tracers — convert outside jit; the
        per-instance memo on ``CSR.grouped`` makes that a one-time
        cost)."""
        if split_threshold is None and merge_threshold is None:
            coo = csr.tocoo()
            nnz = csr.nnz
            padded = max(round_up(max(nnz, 1), nnz_tile), nnz_tile)
            pad = padded - nnz
            pad_row = csr.shape[0] - 1
            rows = jnp.concatenate(
                [coo.rows, jnp.full((pad,), pad_row, jnp.int32)])
            cols = jnp.concatenate([coo.cols, jnp.zeros((pad,), jnp.int32)])
            vals = jnp.concatenate(
                [coo.vals, jnp.zeros((pad,), coo.vals.dtype)])
            return GroupedCOO(rows=rows, cols=cols, vals=vals,
                              shape=csr.shape, nnz=nnz, nnz_tile=nnz_tile)
        if group_size is None:
            raise ValueError(
                "skew grouping needs the schedule's group_size= (heavy "
                "rows are split at group granularity)")
        indptr = _concrete_np(csr.indptr, "skew grouping")
        rows, cols, pos, heavy_tiles = _skew_layout(
            indptr, _concrete_np(csr.indices, "skew grouping"),
            csr.shape, nnz_tile, group_size, split_threshold,
            merge_threshold)
        pos_j = jnp.asarray(pos)
        vals = jnp.zeros((rows.shape[0],),
                         csr.vals.dtype).at[pos_j].set(csr.vals)
        g = GroupedCOO(
            rows=jnp.asarray(rows), cols=jnp.asarray(cols),
            vals=vals, shape=csr.shape, nnz=csr.nnz,
            nnz_tile=nnz_tile,
            skew=(split_threshold, merge_threshold, group_size,
                  heavy_tiles))
        object.__setattr__(g, "_skew_positions", pos_j)
        return g

    def _compact(self):
        """(rows, cols, vals) original-order unpadded triplet views —
        ``[:nnz]`` slices for the trailing-padded layout, a
        :meth:`skew_positions` gather for skew layouts."""
        if self.skew is None:
            return (self.rows[: self.nnz], self.cols[: self.nnz],
                    self.vals[: self.nnz])
        pos = self.skew_positions()
        return self.rows[pos], self.cols[pos], self.vals[pos]

    def regrouped(self, nnz_tile: int, *, group_size: int | None = None,
                  split_threshold: int | None = None,
                  merge_threshold: int | None = None) -> "GroupedCOO":
        """This GroupedCOO re-laid-out for a different tile size and/or
        skew partition, memoized per ``(nnz_tile, group_size, split,
        merge)`` target (the same per-``(format, tile)`` conversion
        cache ``CSR`` has) — a serving loop whose tuned schedule differs
        from the feed's converts once, not per call.  A matching target
        (including a matching skew tuple) returns ``self`` unchanged."""
        want_skew = (split_threshold is not None
                     or merge_threshold is not None)
        if want_skew and group_size is None:
            raise ValueError(
                "skew regrouping needs the schedule's group_size=")
        if nnz_tile == self.nnz_tile:
            if not want_skew and self.skew is None:
                return self
            if (want_skew and self.skew is not None
                    and self.skew[:3] == (split_threshold, merge_threshold,
                                          group_size)):
                return self

        def _build():
            rows_c, cols_c, vals_c = self._compact()
            if not want_skew:
                nnz = self.nnz
                padded = max(round_up(max(nnz, 1), nnz_tile), nnz_tile)
                pad = padded - nnz
                return GroupedCOO(
                    rows=jnp.concatenate(
                        [rows_c,
                         jnp.full((pad,), self.shape[0] - 1, jnp.int32)]),
                    cols=jnp.concatenate(
                        [cols_c, jnp.zeros((pad,), jnp.int32)]),
                    vals=jnp.concatenate(
                        [vals_c, jnp.zeros((pad,), self.vals.dtype)]),
                    shape=self.shape, nnz=nnz, nnz_tile=nnz_tile)
            rows_np = _concrete_np(rows_c, "skew regrouping")
            lengths = np.bincount(rows_np, minlength=self.shape[0])
            indptr = np.concatenate([[0], np.cumsum(lengths)])
            rows, cols, pos, heavy_tiles = _skew_layout(
                indptr, _concrete_np(cols_c, "skew regrouping"),
                self.shape, nnz_tile, group_size, split_threshold,
                merge_threshold)
            pos_j = jnp.asarray(pos)
            vals = jnp.zeros((rows.shape[0],),
                             self.vals.dtype).at[pos_j].set(vals_c)
            g = GroupedCOO(
                rows=jnp.asarray(rows), cols=jnp.asarray(cols),
                vals=vals, shape=self.shape, nnz=self.nnz,
                nnz_tile=nnz_tile,
                skew=(split_threshold, merge_threshold, group_size,
                      heavy_tiles))
            object.__setattr__(g, "_skew_positions", pos_j)
            return g

        return _memoized(self, (self.rows, self.cols, self.vals),
                         ("regrouped", nnz_tile, group_size,
                          split_threshold, merge_threshold), _build)

    def blocked(self, windows: tuple) -> "BlockedCOO":
        """These lanes as a :class:`BlockedCOO` of the same tile,
        memoized per ``windows``; a host pass over concrete indices."""
        def _build():
            rows, cols, vals = self._compact()
            return BlockedCOO.fromcoo(
                _concrete_np(rows, "the blocked layout"),
                _concrete_np(cols, "the blocked layout"), vals, self.shape,
                self.nnz_tile, windows)

        return _memoized(self, (self.rows, self.cols, self.vals),
                         ("blocked", tuple(windows)), _build)

    def todense(self) -> jax.Array:
        """Scatter-add the (padded) triplets into a dense array — padded
        lanes contribute zero by the zero-extension rule."""
        out = jnp.zeros(self.shape, self.vals.dtype)
        return out.at[self.rows, self.cols].add(self.vals)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["rows", "cols", "vals", "tile_rows", "tile_cols"],
    meta_fields=["shape", "nnz", "nnz_tile", "windows"],
)
@dataclasses.dataclass(frozen=True)
class BlockedCOO:
    """COO lanes in 2-D blocks — the feed format of the windowed EB
    launch (``kernels.spmm_eb.spmm_eb_stream``), for a B or an output
    larger than VMEM.

    ``windows = (row_window, col_window)`` cut the output rows and the B
    rows (the matrix's columns) into windows; block ``(r, c)`` holds the
    entries whose row lies in row window ``r`` and column in column
    window ``c``, row-sorted, padded to whole ``nnz_tile`` tiles.  Blocks
    run row window by row window, so a row window's tiles are
    consecutive, and a row window with no entry still gets one tile of
    padding (its output rows are then zeroed and take the epilogue).
    ``rows`` and ``cols`` are each lane's row and column *within* its
    windows, and ``tile_rows`` / ``tile_cols`` (``(num_tiles,)``) each
    tile's windows.  Padded lanes have ``val == 0`` and the row of their
    block's last entry (row 0 in an empty row window), so a window
    product's span does not grow; their column is 0.
    """

    rows: jax.Array  # (nnz_padded,) int32, within the row window
    cols: jax.Array  # (nnz_padded,) int32, within the column window
    vals: jax.Array  # (nnz_padded,)
    tile_rows: jax.Array  # (num_tiles,) int32 row window of each tile
    tile_cols: jax.Array  # (num_tiles,) int32 column window of each tile
    shape: tuple
    nnz: int
    nnz_tile: int
    windows: tuple

    @property
    def nnz_padded(self) -> int:
        """Total lane count including padding (a ``nnz_tile`` multiple)."""
        return self.vals.shape[0]

    @property
    def num_tiles(self) -> int:
        """Grid extent along the nnz axis."""
        return self.nnz_padded // self.nnz_tile

    def positions(self) -> jax.Array:
        """(nnz,) int32: the lane of each entry of the source triplets, in
        their order, so that autodiff can place a fresh value stream
        (``zeros.at[positions].set(vals)``).  Lost on pytree-transformed
        copies."""
        return self.__dict__["_positions"]

    def with_vals(self, vals) -> "BlockedCOO":
        """This layout with ``vals`` (nnz_padded,) as its value stream."""
        out = dataclasses.replace(self, vals=vals)
        object.__setattr__(out, "_positions", self.positions())
        return out

    def global_lanes(self):
        """(rows, cols) of every lane in the matrix's own indices."""
        rw, cw = self.windows
        tile = lambda t: jnp.repeat(t, self.nnz_tile)  # noqa: E731
        return (tile(self.tile_rows) * rw + self.rows,
                tile(self.tile_cols) * cw + self.cols)

    @staticmethod
    def fromcoo(rows, cols, vals, shape, nnz_tile: int,
                windows: tuple) -> "BlockedCOO":
        """The blocked layout of triplets ``rows``/``cols`` (NumPy) and
        ``vals``; ``rows`` need not be sorted."""
        rw, cw = (int(w) for w in windows)
        n_rw, n_cw = -(-shape[0] // rw), -(-max(shape[1], 1) // cw)
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        block = (rows // rw) * n_cw + cols // cw
        order = np.lexsort((cols, rows, block))
        counts = np.bincount(block, minlength=n_rw * n_cw)
        tiles = -(-counts // nnz_tile)
        empty = tiles.reshape(n_rw, n_cw).sum(axis=1) == 0
        tiles.reshape(n_rw, n_cw)[empty, 0] = 1  # one tile a row window
        starts = np.concatenate([[0], np.cumsum(tiles * nnz_tile)])
        firsts = np.concatenate([[0], np.cumsum(counts)])
        sorted_block = block[order]
        slot = starts[sorted_block] + np.arange(order.size) - firsts[sorted_block]
        total = int(starts[-1])
        # each block's padding takes the row of its last entry
        last_row = np.zeros(n_rw * n_cw, np.int64)
        has = counts > 0
        last_row[has] = rows[order[firsts[1:][has] - 1]] % rw
        lane_rows = np.repeat(last_row, tiles * nnz_tile)
        lane_cols = np.zeros(total, np.int64)
        lane_rows[slot] = rows[order] % rw
        lane_cols[slot] = cols[order] % cw
        positions = np.empty(order.size, np.int64)
        positions[order] = slot
        ids = np.repeat(np.arange(n_rw * n_cw), tiles)
        pos_j = jnp.asarray(positions, jnp.int32)
        if isinstance(vals, jax.core.Tracer):
            lane_vals = jnp.zeros((total,), vals.dtype).at[pos_j].set(vals)
        else:  # placed on the host: a memo never holds a traced value
            host = np.asarray(vals)
            lane_vals = np.zeros((total,), host.dtype)
            lane_vals[positions] = host
            lane_vals = jnp.asarray(lane_vals)
        out = BlockedCOO(
            rows=jnp.asarray(lane_rows, jnp.int32),
            cols=jnp.asarray(lane_cols, jnp.int32),
            vals=lane_vals,
            tile_rows=jnp.asarray(ids // n_cw, jnp.int32),
            tile_cols=jnp.asarray(ids % n_cw, jnp.int32),
            shape=tuple(shape), nnz=int(order.size), nnz_tile=nnz_tile,
            windows=(rw, cw))
        object.__setattr__(out, "_positions", pos_j)
        return out


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["cols", "vals"],
    meta_fields=["shape", "width"],
)
@dataclasses.dataclass(frozen=True)
class ELL:
    """Per-row padded format (rows also padded to a row-tile multiple by the
    kernel wrapper). Feed format for the row-split kernel: a grid cell owns
    ``ROW_TILE`` whole rows. Padding cols point at column 0 with val 0."""

    cols: jax.Array  # (n_rows_padded, width) int32
    vals: jax.Array  # (n_rows_padded, width)
    shape: tuple
    width: int

    @property
    def n_rows_padded(self) -> int:
        """Row count padded up to the row tile."""
        return self.vals.shape[0]

    @staticmethod
    def fromcsr(csr: CSR, width: int | None = None, row_tile: int = 8) -> "ELL":
        """CSR -> ELL with rows padded to ``width`` (default: the max row
        length) and the row count to ``row_tile`` (host-side numpy pass —
        requires concrete arrays)."""
        indptr = np.asarray(csr.indptr).astype(np.int64)
        indices = np.asarray(csr.indices)
        vals = np.asarray(csr.vals)
        n_rows = csr.shape[0]
        lengths = indptr[1:] - indptr[:-1]
        w = int(lengths.max()) if len(lengths) and lengths.max() > 0 else 1
        if width is not None:
            if width < w:
                raise ValueError(f"width {width} < max row length {w}")
            w = width
        w = max(w, 1)
        n_pad = round_up(max(n_rows, 1), row_tile)
        ecols = np.zeros((n_pad, w), np.int32)
        # always the source dtype: the empty-vals np.float32 fallback this
        # used to carry silently widened empty bf16/int8 matrices
        evals = np.zeros((n_pad, w), vals.dtype)
        row_ids, pos = _csr_scatter_index(indptr)
        ecols[row_ids, pos] = indices
        evals[row_ids, pos] = vals
        return ELL(cols=jnp.asarray(ecols), vals=jnp.asarray(evals),
                   shape=csr.shape, width=w)

    def todense(self) -> jax.Array:
        """Dense (n_rows, n_cols) array (padding slots contribute 0)."""
        n_rows, _ = self.shape
        rows = jnp.repeat(jnp.arange(self.n_rows_padded), self.width)
        out = jnp.zeros((self.n_rows_padded, self.shape[1]), self.vals.dtype)
        out = out.at[rows, self.cols.reshape(-1)].add(self.vals.reshape(-1))
        return out[:n_rows]


# ---------------------------------------------------------------------------
# Int8 quantized values (DESIGN.md §13)
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["csr", "scales"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class QuantizedCSR:
    """Symmetric per-row int8 quantization of a CSR's values.

    ``csr`` holds the original sparsity pattern with int8 codes as
    values; ``scales`` is the (n_rows,) float32 per-row step so lane
    ``t`` dequantizes as ``vals[t] * scales[row(t)]``.  Scales are
    *segment-aligned*: every lane of a row shares one scale, so the
    kernels dequantize per lane **before** the segment reduction and the
    scatter stays monoid-correct — partial sums combine exactly as in
    the f32 kernel, whichever reduction strategy runs.

    The pattern conversions (``grouped``/``ell``/``tocoo``) live on the
    inner ``csr`` and memoize there as usual; the int8 value stream
    flows through them unchanged (the dtype-preserving padding rule).
    """

    csr: CSR  # int8 values, original pattern
    scales: jax.Array  # (n_rows,) float32

    @property
    def shape(self) -> tuple:
        """Dense (n_rows, n_cols) of the underlying matrix."""
        return self.csr.shape

    @property
    def nnz(self) -> int:
        """Stored-value count."""
        return self.csr.nnz

    def row_lengths(self) -> jax.Array:
        """(n_rows,) per-row nnz counts (fingerprint input)."""
        return self.csr.row_lengths()

    def dequantize(self) -> CSR:
        """Float32 CSR with values ``codes * scales[row]`` (the spec-
        oracle view of this matrix; memoized on the inner CSR)."""
        def _build():
            rows = self.csr.tocoo().rows
            vals = (self.csr.vals.astype(jnp.float32)
                    * jnp.take(self.scales, rows))
            return CSR(indptr=self.csr.indptr, indices=self.csr.indices,
                       vals=vals, shape=self.csr.shape)

        return _memoized(self, (self.csr.vals, self.scales),
                         "dequantized", _build)

    def todense(self) -> jax.Array:
        """Dense f32 array of the dequantized matrix."""
        return self.dequantize().todense()


def quantize_csr(csr: CSR, *, method: str = "absmax",
                 percentile: float = 99.9) -> QuantizedCSR:
    """Quantize a CSR's values to int8 with per-row symmetric scales.

    Calibration (host-side numpy pass; requires concrete arrays):

    - ``"absmax"``    — scale each row by its exact |max| / 127: lossless
      range, precision limited by outliers.
    - ``"percentile"`` — clip the calibration statistic at the global
      ``percentile``-th magnitude before the per-row absmax, so a few
      outlier values don't inflate every scale; quantization saturates
      the clipped outliers at ±127.

    Empty rows get scale 1.0 (nothing to represent; avoids div-by-zero
    on dequant).  Returns a :class:`QuantizedCSR`.
    """
    if method not in ("absmax", "percentile"):
        raise ValueError(
            f"unknown calibration method {method!r}; "
            "expected 'absmax' or 'percentile'")
    vals = _concrete_np(csr.vals, "int8 quantization").astype(np.float32)
    indptr = _concrete_np(csr.indptr, "int8 quantization").astype(np.int64)
    n_rows = csr.shape[0]
    lengths = indptr[1:] - indptr[:-1]
    row_ids = np.repeat(np.arange(n_rows), lengths)
    absv = np.abs(vals)
    if method == "percentile" and absv.size:
        absv = np.minimum(absv, np.percentile(absv, percentile))
    amax = np.zeros(n_rows, np.float32)
    np.maximum.at(amax, row_ids, absv)
    scales = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    codes = np.clip(np.rint(vals / scales[row_ids]), -127, 127)
    inner = CSR(indptr=csr.indptr, indices=csr.indices,
                vals=jnp.asarray(codes.astype(np.int8)), shape=csr.shape)
    return QuantizedCSR(csr=inner, scales=jnp.asarray(scales))


def dequantize(q: QuantizedCSR) -> CSR:
    """Module-level alias of :meth:`QuantizedCSR.dequantize`."""
    return q.dequantize()
