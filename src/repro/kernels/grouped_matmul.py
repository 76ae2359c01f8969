"""Grouped (expert-segment) matmul Pallas kernel — segment group applied to
MoE dispatch (DESIGN.md §4.1).

MoE expert application is sparse-dense hybrid algebra in the paper's DF
formulation: Q₀ = token→expert routing (sparse), ⊗ = expert GEMM,
⊕ = segment-sum over each expert's token segment. Tokens arrive sorted by
expert and *capacity-padded so every token tile belongs to exactly one
expert* — zero extension again: padding tokens multiply real expert
weights and are masked afterwards.

The tile→expert map is scalar-prefetched so the weight BlockSpec can
select the expert block at DMA-schedule time (the TPU analogue of the
runtime writeback-thread election: the *read* side is decided at runtime
here).

The kernel is a planner-rule target (``repro.fuse``): a ``core.Epilogue``
(per-expert bias / activation / dtype cast) runs on the output block at
the last contraction step, so e.g. the MoE expert GEMM's SiLU is one
launch per tile instead of a GEMM pass plus an XLA elementwise pass.
Residuals are not supported here — there is no natural (T_pad, F)
residual operand in the expert-sorted layout.

Grid: (token_tiles, f_tiles, d_tiles) — contraction axis innermost.
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
    pallas_call,
    split_epilogue_refs,
    upcast_f32,
)

_NOOP = Epilogue()


def fit_tile(n: int, tile: int) -> int:
    """Largest power-of-two shrink of ``tile`` that divides ``n`` —
    ``grouped_matmul`` requires exact blocking of the D/F axes, and
    halving preserves the power-of-two grid.  Shared by the dispatch
    path (``models.moe``) and the tuner (``tune.moe``) so both agree on
    what a legal tile is."""
    t = max(1, min(tile, n))
    while n % t and t > 1:
        t //= 2
    return t


def _gmm_kernel(epilogue: Epilogue, narrowed: bool,
                emap_ref, x_ref, w_ref, *refs):
    del emap_ref  # consumed by the index maps
    bias_ref, res_ref, out_ref, acc_ref = split_epilogue_refs(
        refs, epilogue, narrowed)
    acc = out_ref if acc_ref is None else acc_ref

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    # narrow (bf16/fp8) storage upcasts here; accumulation is f32
    x, w3 = upcast_f32(x_ref[...], w_ref[...])  # (TT, DT), (1, DT, FT)
    w = w3[0]  # (DT, FT)
    acc[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    if not epilogue.is_noop or narrowed:
        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _finish():
            apply_epilogue(out_ref, epilogue, bias_ref, res_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=("token_tile", "f_tile", "d_tile", "epilogue"),
)
def grouped_matmul(x, tile_experts, weights, *, bias=None,
                   epilogue: Epilogue = _NOOP, token_tile: int = 128,
                   f_tile: int = 128, d_tile: int = 128):
    """x: (T_pad, D) tokens sorted by expert, T_pad % token_tile == 0;
    tile_experts: (T_pad // token_tile,) int32 expert of each token tile;
    weights: (E, D, F); bias: (E, F) per-expert, required iff
    ``epilogue.bias``. Returns (T_pad, F) in ``epilogue.out_dtype``
    (f32 default) with the epilogue fused onto the output block."""
    t_pad, d = x.shape
    e, dw, f = weights.shape
    assert dw == d and t_pad % token_tile == 0
    assert d % d_tile == 0 and f % f_tile == 0
    assert not epilogue.residual, \
        "grouped_matmul has no residual operand (see module docstring)"
    assert epilogue.bias == (bias is not None)
    if bias is not None:
        assert bias.shape == (e, f), (bias.shape, (e, f))

    out_dtype = jnp.dtype(epilogue.out_dtype or jnp.float32)
    narrowed = out_dtype != jnp.float32

    in_specs = [
        pl.BlockSpec((token_tile, d_tile), lambda i, j, k, emap: (i, k)),
        pl.BlockSpec((1, d_tile, f_tile),
                     lambda i, j, k, emap: (emap[i], k, j)),
    ]
    operands = [tile_experts, x, weights]
    if bias is not None:
        in_specs.append(
            pl.BlockSpec((1, f_tile), lambda i, j, k, emap: (emap[i], j)))
        operands.append(bias)

    grid = (t_pad // token_tile, f // f_tile, d // d_tile)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((token_tile, f_tile),
                               lambda i, j, k, emap: (i, j)),
        scratch_shapes=(
            [pltpu.VMEM((token_tile, f_tile), jnp.float32)]
            if narrowed else []
        ),
    )
    return pallas_call(
        functools.partial(_gmm_kernel, epilogue, narrowed),
        name="grouped_matmul",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t_pad, f), out_dtype),
    )(*operands)
