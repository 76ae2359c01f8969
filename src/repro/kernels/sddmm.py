"""SDDMM Pallas kernel: vals[t] = <A[rows[t]], B[cols[t]]> (* scale[t]).

The second sparse-dense hybrid algebra of the paper (Eq. 2c) — reduction
here runs along two *dense* dimensions, so the segment group degenerates
to a per-lane feature-axis reduce; what Sgap contributes is the nnz-split
tiling + zero extension.

``scale=None`` is a fast path: no all-ones scale operand is materialized
or streamed.  Padded lanes then produce garbage dot products — which is
*legal* zero extension, because GroupedCOO padding is strictly trailing
and the ``ops.sddmm`` wrapper crops ``out[:nnz]``; with a scale the
padded entries carry ``scale = 0`` and are masked in-kernel as before.

Grid: (nnz_tiles, d_tiles) — feature axis innermost, accumulating the
per-lane dot products.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import pallas_call, upcast_f32


def _sddmm_kernel(*refs, has_scale: bool):
    if has_scale:
        rows_ref, cols_ref, scale_ref, a_ref, b_ref, out_ref = refs
    else:
        rows_ref, cols_ref, a_ref, b_ref, out_ref = refs
        scale_ref = None

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[...]
    cols = cols_ref[...]
    # narrow (bf16/fp8) operands upcast here; the dot accumulates in f32
    a, b = upcast_f32(a_ref[...], b_ref[...])  # (M, Dt), (N, Dt)
    ga = jnp.take(a, rows, axis=0)  # (T, Dt)
    gb = jnp.take(b, cols, axis=0)  # (T, Dt)
    out_ref[...] += jnp.sum(ga * gb, axis=-1)

    if scale_ref is not None:
        @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
        def _scale():
            out_ref[...] *= upcast_f32(scale_ref[...])


@functools.partial(
    jax.jit, static_argnames=("nnz_tile", "d_tile"))
def sddmm(rows, cols, a, b, scale=None, *, nnz_tile: int = 256,
          d_tile: int = 128):
    """rows/cols/scale: (nnz_pad,) padded to nnz_tile (scale 0 on padding,
    or scale omitted entirely — the wrapper crops trailing pad lanes);
    a: (M, D), b: (N, D) with D padded to d_tile by the wrapper."""
    nnz_pad = rows.shape[0]
    m, d = a.shape
    n, _ = b.shape
    assert nnz_pad % nnz_tile == 0 and d % d_tile == 0
    grid = (nnz_pad // nnz_tile, d // d_tile)
    has_scale = scale is not None
    operands = [rows, cols] + ([scale] if has_scale else []) + [a, b]
    lane_spec = pl.BlockSpec((nnz_tile,), lambda i, u: (i,))
    in_specs = [lane_spec] * (3 if has_scale else 2) + [
        pl.BlockSpec((m, d_tile), lambda i, u: (0, u)),
        pl.BlockSpec((n, d_tile), lambda i, u: (0, u)),
    ]
    return pallas_call(
        functools.partial(_sddmm_kernel, has_scale=has_scale),
        name="sddmm",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((nnz_tile,), lambda i, u: (i,)),
        out_shape=jax.ShapeDtypeStruct((nnz_pad,), jnp.float32),
    )(*operands)
