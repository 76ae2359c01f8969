"""nnz-split (EB) segment-group SpMM Pallas kernel — the paper's
``{<1 nnz, c col>, r}`` algorithm (Sgap §6.2, Listing 6), TPU-native.

Grid: (col_tiles, nnz_tiles) — nnz innermost so consecutive grid steps
revisit the same output block and accumulation is race-free.

Per grid cell (one ``NNZ_TILE × COL_TILE`` block):
  1. gather dense rows      B[cols]            one dynamic row window of
                                               the resident B block per
                                               lane, its index a scalar
                                               read from SMEM (zero
                                               extension: padded lanes
                                               gather row 0, val 0)
  2. scale by values        P = vals ⊙ B[cols]
  3. segment-group reduce   'segment' on a tile whose rows fit
                            (128 + 8)-row output windows, one a
                            128-lane chunk: one MXU product a chunk of
                            a 0/1 row-match matrix and the partials,
                            added into its window;
                            otherwise, and for every other strategy,
                            the registry's per-run masked reduce +
                            runtime writeback (see kernels/common.py)
  4. on the *last* nnz step of a column block: the fused epilogue
     (bias / activation / residual / dtype cast — DESIGN.md §8), so a
     GCN layer's ``act(A @ XW + b)`` is one kernel instead of three HBM
     round trips.  This epilogue slot is what the fusion planner's
     ``epilogue-fold`` rule targets (``repro.fuse``, DESIGN.md §10):
     ewise chain nodes legal under ``Epilogue.extended`` land here.

VMEM working set (``vmem_need_eb``): the B block (K × COL_TILE) and the
out block (n_rows × COL_TILE), both resident for a whole column block,
both lane-padded to 128 and double-buffered unless one column tile
covers N, plus the partials (NNZ_TILE × COL_TILE) and, where the window
reduce engages, the row ids as a second, VMEM lane block.  The launch
asks the compiler for that plus headroom (``common.pallas_call``).

Operands whose blocks exceed the VMEM budget run windowed, as the launch
``spmm_eb_stream`` (:func:`spmm_eb_stream`, same kernel body): the lanes
come as a 2-D block partition (``formats.BlockedCOO``: row window ×
column window, each block's tiles consecutive and a row window's blocks
consecutive), each tile's two window ids are scalar-prefetched
(``PrefetchScalarGridSpec``), and the block specs fetch the B rows of the
tile's column window and keep the output rows of its row window resident
across that window's tiles.  The output window is zeroed on its first
tile and takes the epilogue on its last.  :func:`eb_windows` sizes the
windows from the budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.schedule import Epilogue
from ..sparse.formats import round_up
from .common import (
    apply_epilogue,
    block_buffers,
    group_reduce_scatter,
    pallas_call,
    segment_window,
    split_epilogue_refs,
    upcast_f32,
    vmem_bytes,
    window_reduce_scatter,
)

_NOOP = Epilogue()


def _spmm_eb_kernel(rows_ref, cols_ref, vals_ref, b_ref, *refs,
                    group_size: int, strategy: str, heavy_tiles: int,
                    epilogue: Epilogue, narrowed: bool, quantized: bool,
                    window: bool, first=None, last=None):
    """One grid step.  ``first`` / ``last`` say whether this nnz step is
    the first / last one of its output block (default: of the whole nnz
    axis, where one output block spans it)."""
    if window:
        lanes_ref, *refs = refs
    if quantized:
        scales_ref, *refs = refs
    *refs, part_ref = refs
    bias_ref, res_ref, out_ref, acc_ref = split_epilogue_refs(
        refs, epilogue, narrowed)
    # out_dtype narrowing: accumulate in the f32 scratch, cast only at
    # the final store (out_ref doubles as the accumulator otherwise)
    acc = out_ref if acc_ref is None else acc_ref

    @pl.when(pl.program_id(1) == 0 if first is None else first)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    # gather: lane t takes dense row cols[t], a dynamic one-row window of
    # the resident B block addressed by a scalar from SMEM.  Storage may
    # be narrow (bf16/fp8) or int8 codes — all arithmetic is f32 from
    # here on (the upcast_f32 accumulation contract)
    def gather(t, c):
        row = upcast_f32(b_ref[pl.ds(cols_ref[0, t], 1), :])
        if quantized:
            # per-lane dequant *before* the segment reduce: scales are
            # per-row (segment-aligned), so partials combine exactly as
            # in the f32 kernel and the scatter stays monoid-correct.
            # Padded lanes take the pad row's scale with val 0 — still 0
            row = row * scales_ref[0, rows_ref[0, t]]
        part_ref[pl.ds(t, 1), :] = row
        return c

    jax.lax.fori_loop(0, part_ref.shape[0], gather, 0)
    # scale by values: the (1, T) lane row turns into a (T, 1) column
    part_ref[...] = part_ref[...] * upcast_f32(vals_ref[...]).T

    def reduce():
        if window:
            window_reduce_scatter(rows_ref, lanes_ref, part_ref, acc,
                                  group_size, strategy)
        else:
            group_reduce_scatter(rows_ref, part_ref, acc, group_size,
                                 strategy)

    if heavy_tiles > 0 and strategy != "parallel":
        # two-level skew layout (DESIGN.md §11): the leading heavy tiles
        # hold single-row groups, so they run the registry's 'parallel'
        # realization — one plain reduce + one read-modify-write per
        # group, the accumulate-style cross-group combine for split rows
        @pl.when(pl.program_id(1) < heavy_tiles)
        def _heavy():
            group_reduce_scatter(rows_ref, part_ref, acc, group_size,
                                 "parallel")

        @pl.when(pl.program_id(1) >= heavy_tiles)
        def _tail():
            reduce()
    else:
        reduce()

    if not epilogue.is_noop:
        @pl.when(pl.program_id(1) == pl.num_programs(1) - 1 if last is None
                 else last)
        def _epilogue():
            apply_epilogue(out_ref, epilogue, bias_ref, res_ref,
                           acc_ref=acc_ref)


def vmem_need_eb(k: int, n_rows: int, *, nnz_tile: int, col_tile: int,
                 n: int | None = None, b_dtype=jnp.float32,
                 vals_dtype=jnp.float32, epilogue: Epilogue = _NOOP,
                 strategy: str = "segment", windows=None) -> int:
    """Padded, buffered VMEM bytes of one ``spmm_eb`` launch over an
    N-wide dense operand (``n=None``: more than one column tile): the
    whole-K B column block and the whole-n_rows output column block
    (single-buffered when one column tile covers N, double-buffered
    otherwise), the value lanes, the partials scratch, and the
    epilogue's bias/residual blocks and f32 accumulator, and the row ids'
    VMEM lane block where the window reduce engages
    (``common.segment_window``).  Row/column indices and scales live in
    SMEM.

    ``windows=(row_window, col_window)`` counts the ``spmm_eb_stream``
    launch instead: the B block is ``col_window`` rows and the output
    (and residual) block ``row_window`` rows, each double-buffered
    unless it never moves (one window and one column tile).

    This is the compiler's scoped allocation when XLA leaves the
    operands in HBM (``tests/test_tpu_compile.py`` checks it to the
    byte; for the windowed launch at ogbn-arxiv's size the compiler
    leaves the lane and bias blocks, a few KiB, out of it).  XLA may
    place narrow operands in VMEM itself — at PubMed
    size with 16 columns it places B, the output and the value lanes
    there (``S(1)`` in the compiled HLO).  The kernel then reads them
    in place and only the partials scratch stays scoped; those blocks
    still take VMEM, XLA's instead of the kernel's, so the count bounds
    what the launch holds and the limit it asks for over-reserves by
    them."""
    out_dtype = jnp.dtype(epilogue.out_dtype or jnp.float32)
    one_tile = n == col_tile
    b_rows, out_rows = (k, n_rows) if windows is None else windows[::-1]
    bb = block_buffers(1 if one_tile and b_rows >= k else 2)
    ob = block_buffers(1 if one_tile and out_rows >= n_rows else 2)
    need = (vmem_bytes((b_rows, col_tile), b_dtype, bb)
            + vmem_bytes((out_rows, col_tile), out_dtype, ob)
            + vmem_bytes((1, nnz_tile), vals_dtype, 2)
            + vmem_bytes((nnz_tile, col_tile), jnp.float32))
    if segment_window(strategy, out_rows, nnz_tile) is not None:
        need += vmem_bytes((1, nnz_tile), jnp.int32, 2)
    if epilogue.bias:
        need += vmem_bytes((1, col_tile), jnp.float32,
                           block_buffers(1 if one_tile else 2))
    if epilogue.residual:
        need += vmem_bytes((out_rows, col_tile), jnp.float32, ob)
    if out_dtype != jnp.float32:
        need += vmem_bytes((out_rows, col_tile), jnp.float32)
    return need


def eb_windows(k: int, n_rows: int, *, budget: int, **need):
    """``(row_window, col_window)`` of the ``spmm_eb_stream`` launch over
    a ``k``-row B and ``n_rows`` output rows, or None where the resident
    launch's blocks (``vmem_need_eb(k, n_rows, **need)``) fit ``budget``
    bytes.

    B is fetched once a block a tile visits, so fewer row windows move
    fewer bytes: the output window is the largest that holds at most
    half the budget (rows split evenly, 8-aligned), then the column
    window the largest that fits what is left.  A budget that not even
    8-row windows fit raises ``ValueError``."""
    if vmem_need_eb(k, n_rows, **need) <= budget:
        return None

    def split(extent, parts):
        return round_up(-(-extent // parts), 8)

    def fits(rw, cw, limit):
        return vmem_need_eb(k, n_rows, windows=(rw, cw), **need) <= limit

    # the output's share alone: with 8-row B windows, under half the budget
    parts = 1
    while split(n_rows, parts) > 8 and not fits(split(n_rows, parts), 8,
                                                budget // 2):
        parts += 1
    rw = split(n_rows, parts)
    parts = 1
    while split(k, parts) > 8 and not fits(rw, split(k, parts), budget):
        parts += 1
    cw = split(k, parts)
    if not fits(rw, cw, budget):
        raise ValueError(f"no eb layout of {k} B rows and {n_rows} output "
                         f"rows fits {budget} bytes of VMEM")
    return rw, cw


def _lanes(x, nnz_tile: int):
    """Lanes as (tiles, 1, nnz_tile): one (1, nnz_tile) block a step."""
    return x.reshape(-1, 1, nnz_tile)


def _eb_call(rows, cols, vals, b, *, n_rows, nnz_tile, col_tile, group_size,
             strategy, heavy_tiles, epilogue, scales, bias, residual,
             interpret, stream=None):
    """The ``pallas_call`` of both eb launches over shape-strict operands.
    ``stream`` is None for the resident launch, else ``(tile_rows,
    tile_cols, row_window, col_window)`` of the windowed one."""
    nnz_pad = vals.shape[0]
    k, n = b.shape
    assert nnz_pad % nnz_tile == 0 and n % col_tile == 0, (nnz_pad, n)
    grid = (n // col_tile, nnz_pad // nnz_tile)
    if stream is None:
        out_rows, b_rows = n_rows, k
        out_block = lambda j, i, *_: (0, j)  # noqa: E731
        b_block = out_block
        prefetch = []
    else:
        tile_rows, tile_cols, out_rows, b_rows = stream
        assert tile_rows.shape == tile_cols.shape == (grid[1],), grid
        out_block = lambda j, i, tr, tc: (tr[i], j)  # noqa: E731
        b_block = lambda j, i, tr, tc: (tc[i], j)  # noqa: E731
        prefetch = [tile_rows, tile_cols]

    def lane_spec(space=None):
        return pl.BlockSpec((None, 1, nnz_tile), lambda j, i, *_: (i, 0, 0),
                            memory_space=space)

    # indices to SMEM (scalar reads), values to VMEM
    operands = [_lanes(rows, nnz_tile), _lanes(cols, nnz_tile),
                _lanes(vals, nnz_tile), b]
    in_specs = [lane_spec(pltpu.SMEM), lane_spec(pltpu.SMEM), lane_spec(),
                pl.BlockSpec((b_rows, col_tile), b_block)]
    window = segment_window(strategy, out_rows, nnz_tile) is not None
    if window:
        # the row ids once more, as a VMEM lane block: the window's 0/1
        # row-match matrix and its bounds are computed from them as vectors
        operands.append(_lanes(rows, nnz_tile))
        in_specs.append(lane_spec())
    quantized = scales is not None
    if quantized:
        assert stream is None, "int8 codes run the resident launch only"
        assert scales.shape == (n_rows,), (scales.shape, n_rows)
        operands.append(scales.reshape(1, n_rows))
        in_specs.append(pl.BlockSpec((1, n_rows), lambda j, i: (0, 0),
                                     memory_space=pltpu.SMEM))
    if epilogue.bias:
        assert bias is not None and bias.shape == (1, n), (n, bias)
        operands.append(bias)
        in_specs.append(pl.BlockSpec((1, col_tile), lambda j, i, *_: (0, j)))
    if epilogue.residual:
        assert residual is not None and residual.shape == (n_rows, n)
        operands.append(residual)
        in_specs.append(pl.BlockSpec((out_rows, col_tile), out_block))
    out_dtype = jnp.dtype(epilogue.out_dtype or jnp.float32)
    narrowed = out_dtype != jnp.float32
    scratch = ([pltpu.VMEM((out_rows, col_tile), jnp.float32)]
               if narrowed else [])
    scratch.append(pltpu.VMEM((nnz_tile, col_tile), jnp.float32))

    kernel = functools.partial(
        _spmm_eb_kernel, group_size=group_size, strategy=strategy,
        heavy_tiles=heavy_tiles, epilogue=epilogue, narrowed=narrowed,
        quantized=quantized, window=window)
    grid_spec = dict(
        grid=grid, in_specs=in_specs,
        out_specs=pl.BlockSpec((out_rows, col_tile), out_block),
        scratch_shapes=scratch)
    if stream is not None:
        kernel = functools.partial(_stream_kernel, kernel)
        grid_spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), **grid_spec))
    return pallas_call(
        kernel,
        name="spmm_eb" if stream is None else "spmm_eb_stream",
        out_shape=jax.ShapeDtypeStruct((n_rows, n), out_dtype),
        vmem_need=vmem_need_eb(
            k, n_rows, nnz_tile=nnz_tile, col_tile=col_tile, n=n,
            b_dtype=b.dtype, vals_dtype=vals.dtype, epilogue=epilogue,
            strategy=strategy,
            windows=None if stream is None else (out_rows, b_rows)),
        interpret=interpret,
        **grid_spec,
    )(*prefetch, *operands)


def _stream_kernel(kernel, tile_rows_ref, tile_cols_ref, *refs):
    """The windowed launch's grid step: the eb kernel body, its output
    block zeroed on the first tile of its row window and finished on the
    last (a row window's tiles are consecutive)."""
    del tile_cols_ref  # read by the block specs alone
    i, n = pl.program_id(1), pl.num_programs(1)
    win = tile_rows_ref[i]
    first = jnp.logical_or(i == 0, tile_rows_ref[jnp.maximum(i - 1, 0)] != win)
    last = jnp.logical_or(i == n - 1,
                          tile_rows_ref[jnp.minimum(i + 1, n - 1)] != win)
    kernel(*refs, first=first, last=last)


_STATIC = ("n_rows", "nnz_tile", "col_tile", "group_size", "strategy",
           "epilogue", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC + ("heavy_tiles",))
def spmm_eb(rows, cols, vals, b, *, n_rows: int, nnz_tile: int = 256,
            col_tile: int = 128, group_size: int = 32,
            strategy: str = "segment", heavy_tiles: int = 0,
            epilogue: Epilogue = _NOOP, scales=None,
            bias=None, residual=None, interpret: bool | None = None):
    """out (n_rows, N) = scatter-reduce over padded COO triplets × B,
    with the fused ``epilogue`` applied to each output block on its last
    reduction step (``bias`` (1, N) and ``residual`` (n_rows, N) are
    required/forbidden per the epilogue's flags).

    Inputs must be pre-padded: len(vals) % nnz_tile == 0 (see
    ``formats.GroupedCOO``) and b.shape[1] % col_tile == 0 (``ops.spmm``
    does the column padding).  ``heavy_tiles`` (static, from a skew
    ``GroupedCOO``'s metadata) marks the leading nnz tiles whose groups
    are single-row by construction: those run the 'parallel' realization
    regardless of ``strategy`` (DESIGN.md §11).

    ``scales`` (n_rows,) f32, when given, selects the quantized value
    path (DESIGN.md §13): ``vals`` holds int8 codes and every lane is
    dequantized ``val * scales[row]`` before the segment reduce.  The
    scale vector stays resident in SMEM across nnz steps (constant index
    map) — the dequant adds no per-nnz HBM traffic.

    Under 'segment', a tile whose rows fit its windows of output rows
    reduces as MXU products (``common.window_reduce_scatter``), in any
    lane order.

    ``interpret`` defaults to the backend's answer (``common.pallas_call``);
    a test compiles for a described TPU by passing ``False``.
    """
    return _eb_call(rows, cols, vals, b, n_rows=n_rows, nnz_tile=nnz_tile,
                    col_tile=col_tile, group_size=group_size,
                    strategy=strategy, heavy_tiles=heavy_tiles,
                    epilogue=epilogue, scales=scales, bias=bias,
                    residual=residual, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC + ("row_window",
                                                       "col_window"))
def spmm_eb_stream(rows, cols, vals, tile_rows, tile_cols, b, *, n_rows: int,
                   row_window: int, col_window: int, nnz_tile: int = 256,
                   col_tile: int = 128, group_size: int = 32,
                   strategy: str = "segment", epilogue: Epilogue = _NOOP,
                   bias=None, residual=None, interpret: bool | None = None):
    """:func:`spmm_eb` over operands windowed to fit VMEM: the lanes of a
    ``formats.BlockedCOO`` (``rows`` within their row window, ``cols``
    within their column window, each tile's windows ``tile_rows`` and
    ``tile_cols``, a row window's tiles consecutive and at least one a
    row window), B fetched ``col_window`` rows at a time, the output
    kept ``row_window`` rows at a time.  The last window of either may
    run past the array: its rows past the end are never gathered, and
    never written back.  Same epilogue operands as :func:`spmm_eb`; no
    int8 codes, no skew layout."""
    return _eb_call(rows, cols, vals, b, n_rows=n_rows, nnz_tile=nnz_tile,
                    col_tile=col_tile, group_size=group_size,
                    strategy=strategy, heavy_tiles=0, epilogue=epilogue,
                    scales=None, bias=bias, residual=residual,
                    interpret=interpret,
                    stream=(tile_rows, tile_cols, row_window, col_window))
