"""Row-split (RB) SpMM Pallas kernel — the paper's ``{<g row, c col>, 1}``
family (parallel reduction: exactly one writeback per row).

Feed format: ELL (per-row padded, see ``formats.ELL``) — padding is the
zero extension the paper legitimizes: padded slots gather B[0] scaled by
0.0 and flow through the vector datapath unpredicated.

Grid: (row_tiles, col_tiles, width_tiles) — width innermost, accumulating
into the same (ROW_TILE × COL_TILE) output block; the fused epilogue
(``core.Epilogue``: bias / activation / residual / dtype cast) runs on
the last width step, when the block holds the fully-reduced row.  Like
the EB kernel's, this epilogue slot is a fusion-planner target
(``repro.fuse`` ``epilogue-fold`` rule, DESIGN.md §10).
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
    pallas_call,
    split_epilogue_refs,
    upcast_f32,
    vmem_bytes,
)

_NOOP = Epilogue()


def _spmm_rb_kernel(cols_ref, vals_ref, b_ref, *refs,
                    epilogue: Epilogue, narrowed: bool, quantized: bool):
    if quantized:
        scales_ref, *refs = refs
    *refs, g_ref = refs
    bias_ref, res_ref, out_ref, acc_ref = split_epilogue_refs(
        refs, epilogue, narrowed)
    # out_dtype narrowing: accumulate in the f32 scratch, cast only at
    # the final store (out_ref doubles as the accumulator otherwise)
    acc = out_ref if acc_ref is None else acc_ref

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    # narrow (bf16/fp8) or int8 storage upcasts here; reduction is f32
    vals = upcast_f32(vals_ref[...])  # (R, Wt)
    r, wt = vals.shape
    for i in range(r):
        # gather row i's slots: one dynamic row window of the resident B
        # block per slot, its column index a scalar read from SMEM
        def gather(w, c, i=i):
            g_ref[pl.ds(w, 1), :] = upcast_f32(
                b_ref[pl.ds(cols_ref[i, w], 1), :])
            return c

        jax.lax.fori_loop(0, wt, gather, 0)
        row = jnp.sum(g_ref[...] * vals[i:i + 1, :].T, axis=0,
                      keepdims=True)  # (1, C)
        if quantized:
            # per-row scale: this cell owns whole rows, so dequant is
            # one scalar factor on the row's reduced partial
            row = row * scales_ref[0, i]
        acc[i:i + 1, :] += row.astype(acc.dtype)

    if not epilogue.is_noop:
        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _epilogue():
            apply_epilogue(out_ref, epilogue, bias_ref, res_ref,
                           acc_ref=acc_ref)


def rb_width_tile(width: int) -> int:
    """Width tile of an ELL slab: the whole (padded) width up to 128
    slots, else 128 — Mosaic takes a block's last dim whole or in
    multiples of 128 lanes."""
    return max(1, width) if width <= 128 else 128


def vmem_need_rb(k: int, *, row_tile: int, col_tile: int, width_tile: int,
                 n: int | None = None, b_dtype=jnp.float32,
                 vals_dtype=jnp.float32, epilogue: Epilogue = _NOOP) -> int:
    """Padded, buffered VMEM bytes of one ``spmm_rb`` launch over an
    N-wide dense operand (``n=None``: more than one column tile): the
    whole-K B column block (single-buffered when one column tile covers
    N), the ELL value slab, the output block, the gathered-slot scratch,
    and the epilogue's bias/residual blocks and f32 accumulator.  Column
    indices and scales live in SMEM."""
    out_dtype = jnp.dtype(epilogue.out_dtype or jnp.float32)
    cb = block_buffers(1 if n == col_tile else 2)
    need = (vmem_bytes((k, col_tile), b_dtype, cb)
            + vmem_bytes((row_tile, width_tile), vals_dtype, 2)
            + vmem_bytes((row_tile, col_tile), out_dtype, 2)
            + vmem_bytes((width_tile, col_tile), jnp.float32))
    if epilogue.bias:
        need += vmem_bytes((1, col_tile), jnp.float32, cb)
    if epilogue.residual:
        need += vmem_bytes((row_tile, col_tile), jnp.float32, 2)
    if out_dtype != jnp.float32:
        need += vmem_bytes((row_tile, col_tile), jnp.float32)
    return need


@functools.partial(
    jax.jit,
    static_argnames=("row_tile", "col_tile", "width_tile", "epilogue",
                     "interpret"),
)
def spmm_rb(ecols, evals, b, *, row_tile: int = 8, col_tile: int = 128,
            width_tile: int | None = None, epilogue: Epilogue = _NOOP,
            scales=None, bias=None, residual=None,
            interpret: bool | None = None):
    """out (R_pad, N) from ELL arrays (R_pad, W) and dense B (K, N), with
    the fused ``epilogue`` applied per output block on its last width
    step (``bias`` (1, N) / ``residual`` (R_pad, N) per its flags).

    R_pad % row_tile == 0 and N % col_tile == 0 are the wrapper's job
    (``ops.spmm``); W is padded to width_tile (default
    :func:`rb_width_tile`) here.

    ``scales`` (R_pad,) f32, when given, selects the quantized value
    path (DESIGN.md §13): ``evals`` holds int8 codes dequantized
    ``val * scales[row]`` before the width reduction (padded rows carry
    val 0, so their scale is irrelevant).

    ``interpret`` defaults to the backend's answer (``common.pallas_call``);
    a test compiles for a described TPU by passing ``False``.
    """
    r_pad, w = ecols.shape
    k, n = b.shape
    if width_tile is None:
        width_tile = rb_width_tile(w)
    w_pad = ((w + width_tile - 1) // width_tile) * width_tile
    if w_pad != w:
        pad = w_pad - w
        ecols = jnp.pad(ecols, ((0, 0), (0, pad)))
        evals = jnp.pad(evals, ((0, 0), (0, pad)))
    assert r_pad % row_tile == 0 and n % col_tile == 0

    grid = (r_pad // row_tile, n // col_tile, w_pad // width_tile)
    operands = [ecols, evals, b]
    in_specs = [
        pl.BlockSpec((row_tile, width_tile), lambda i, j, u: (i, u),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((row_tile, width_tile), lambda i, j, u: (i, u)),
        pl.BlockSpec((k, col_tile), lambda i, j, u: (0, j)),
    ]
    quantized = scales is not None
    if quantized:
        assert scales.shape == (r_pad,), (scales.shape, r_pad)
        operands.append(scales.reshape(-1, 1, row_tile))
        in_specs.append(pl.BlockSpec((None, 1, row_tile),
                                     lambda i, j, u: (i, 0, 0),
                                     memory_space=pltpu.SMEM))
    if epilogue.bias:
        assert bias is not None and bias.shape == (1, n), (n, bias)
        operands.append(bias)
        in_specs.append(pl.BlockSpec((1, col_tile), lambda i, j, u: (0, j)))
    if epilogue.residual:
        assert residual is not None and residual.shape == (r_pad, n)
        operands.append(residual)
        in_specs.append(
            pl.BlockSpec((row_tile, col_tile), lambda i, j, u: (i, j)))
    out_dtype = jnp.dtype(epilogue.out_dtype or jnp.float32)
    narrowed = out_dtype != jnp.float32
    scratch = ([pltpu.VMEM((row_tile, col_tile), jnp.float32)]
               if narrowed else [])
    scratch.append(pltpu.VMEM((width_tile, col_tile), jnp.float32))

    kernel = functools.partial(_spmm_rb_kernel, epilogue=epilogue,
                               narrowed=narrowed, quantized=quantized)
    return pallas_call(
        kernel,
        name="spmm_rb",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((row_tile, col_tile), lambda i, j, u: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r_pad, n), out_dtype),
        scratch_shapes=scratch,
        vmem_need=vmem_need_rb(k, row_tile=row_tile, col_tile=col_tile,
                               width_tile=width_tile, n=n, b_dtype=b.dtype,
                               vals_dtype=evals.dtype, epilogue=epilogue),
        interpret=interpret,
    )(*operands)
