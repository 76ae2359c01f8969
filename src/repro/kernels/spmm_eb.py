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
asks the compiler for that plus headroom (``common.pallas_call``); operands
whose blocks exceed the chip's VMEM are refused, not windowed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.schedule import Epilogue
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
                    window: bool):
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

    @pl.when(pl.program_id(1) == 0)
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
        @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
        def _epilogue():
            apply_epilogue(out_ref, epilogue, bias_ref, res_ref,
                           acc_ref=acc_ref)


def vmem_need_eb(k: int, n_rows: int, *, nnz_tile: int, col_tile: int,
                 n: int | None = None, b_dtype=jnp.float32,
                 vals_dtype=jnp.float32, epilogue: Epilogue = _NOOP,
                 strategy: str = "segment") -> int:
    """Padded, buffered VMEM bytes of one ``spmm_eb`` launch over an
    N-wide dense operand (``n=None``: more than one column tile): the
    whole-K B column block and the whole-n_rows output column block
    (single-buffered when one column tile covers N, double-buffered
    otherwise), the value lanes, the partials scratch, and the
    epilogue's bias/residual blocks and f32 accumulator, and the row ids'
    VMEM lane block where the window reduce engages
    (``common.segment_window``).  Row/column indices and scales live in
    SMEM.

    This is the compiler's scoped allocation when XLA leaves the
    operands in HBM (``tests/test_tpu_compile.py`` checks it to the
    byte).  XLA may place narrow operands in VMEM itself — at PubMed
    size with 16 columns it places B, the output and the value lanes
    there (``S(1)`` in the compiled HLO).  The kernel then reads them
    in place and only the partials scratch stays scoped; those blocks
    still take VMEM, XLA's instead of the kernel's, so the count bounds
    what the launch holds and the limit it asks for over-reserves by
    them."""
    out_dtype = jnp.dtype(epilogue.out_dtype or jnp.float32)
    cb = block_buffers(1 if n == col_tile else 2)
    need = (vmem_bytes((k, col_tile), b_dtype, cb)
            + vmem_bytes((n_rows, col_tile), out_dtype, cb)
            + vmem_bytes((1, nnz_tile), vals_dtype, 2)
            + vmem_bytes((nnz_tile, col_tile), jnp.float32))
    if segment_window(strategy, n_rows, nnz_tile) is not None:
        need += vmem_bytes((1, nnz_tile), jnp.int32, 2)
    if epilogue.bias:
        need += vmem_bytes((1, col_tile), jnp.float32, cb)
    if epilogue.residual:
        need += vmem_bytes((n_rows, col_tile), jnp.float32, cb)
    if out_dtype != jnp.float32:
        need += vmem_bytes((n_rows, col_tile), jnp.float32)
    return need


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "nnz_tile", "col_tile", "group_size",
                     "strategy", "heavy_tiles", "epilogue", "interpret"),
)
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
    nnz_pad = vals.shape[0]
    k, n = b.shape
    assert nnz_pad % nnz_tile == 0 and n % col_tile == 0, (nnz_pad, n)
    grid = (n // col_tile, nnz_pad // nnz_tile)

    # lanes travel as (tiles, 1, nnz_tile): one (1, nnz_tile) block per
    # nnz step — indices to SMEM (scalar reads), values to VMEM
    def lanes(x):
        return x.reshape(-1, 1, nnz_tile)

    def lane_spec(space=None):
        return pl.BlockSpec((None, 1, nnz_tile), lambda j, i: (i, 0, 0),
                            memory_space=space)

    operands = [lanes(rows), lanes(cols), lanes(vals), b]
    in_specs = [
        lane_spec(pltpu.SMEM),
        lane_spec(pltpu.SMEM),
        lane_spec(),
        pl.BlockSpec((k, col_tile), lambda j, i: (0, j)),
    ]
    window = segment_window(strategy, n_rows, nnz_tile) is not None
    if window:
        # the row ids once more, as a VMEM lane block: the window's 0/1
        # row-match matrix and its bounds are computed from them as vectors
        operands.append(lanes(rows))
        in_specs.append(lane_spec())
    quantized = scales is not None
    if quantized:
        assert scales.shape == (n_rows,), (scales.shape, n_rows)
        operands.append(scales.reshape(1, n_rows))
        in_specs.append(pl.BlockSpec((1, n_rows), lambda j, i: (0, 0),
                                     memory_space=pltpu.SMEM))
    if epilogue.bias:
        assert bias is not None and bias.shape == (1, n), (n, bias)
        operands.append(bias)
        in_specs.append(pl.BlockSpec((1, col_tile), lambda j, i: (0, j)))
    if epilogue.residual:
        assert residual is not None and residual.shape == (n_rows, n)
        operands.append(residual)
        in_specs.append(
            pl.BlockSpec((n_rows, col_tile), lambda j, i: (0, j)))
    out_dtype = jnp.dtype(epilogue.out_dtype or jnp.float32)
    narrowed = out_dtype != jnp.float32
    scratch = [pltpu.VMEM((n_rows, col_tile), jnp.float32)] if narrowed else []
    scratch.append(pltpu.VMEM((nnz_tile, col_tile), jnp.float32))

    kernel = functools.partial(
        _spmm_eb_kernel, group_size=group_size, strategy=strategy,
        heavy_tiles=heavy_tiles, epilogue=epilogue, narrowed=narrowed,
        quantized=quantized, window=window)
    return pallas_call(
        kernel,
        name="spmm_eb",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((n_rows, col_tile), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n_rows, n), out_dtype),
        scratch_shapes=scratch,
        vmem_need=vmem_need_eb(k, n_rows, nnz_tile=nnz_tile,
                               col_tile=col_tile, n=n, b_dtype=b.dtype,
                               vals_dtype=vals.dtype, epilogue=epilogue,
                               strategy=strategy),
        interpret=interpret,
    )(*operands)
