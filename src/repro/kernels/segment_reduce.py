"""Standalone segment-group reduce kernel:
out[s] = ⨁_{t: seg[t]=s} data[t] for a registered strategy × monoid ⨁.

The paper's ``segReduceWarp<T, G>`` macro instruction (Sgap §5.3) as a
first-class Pallas kernel: the same group machinery as ``spmm_eb`` minus
the gather/multiply front-end. Used directly by the SSD chunk combine,
the fused-attention row statistics, and as the microbenchmark target for
Table 1/2.

``op`` selects the reduction monoid ('add' default, 'max', 'min') — the
monoid generalization of the zero-extension rule pads ragged inputs with
the monoid *identity* instead of zero: padded lanes target segment
``num_segments - 1`` carrying identity rows, so they flow through the
datapath and contribute nothing, for any monoid.  Untouched segments
come out as the identity (matching ``jax.ops.segment_max`` etc.).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.schedule import get_strategy
from ..sparse.formats import round_up
from .common import (
    block_buffers,
    group_reduce_scatter,
    pallas_call,
    vmem_bytes,
)


def _segred_kernel(seg_ref, data_ref, out_ref, *scratch, group_size,
                   strategy, op):
    # identity resolved through the registry: a strategy registered with
    # its own combine/identity initializes with *its* identity
    identity = get_strategy(strategy, op=op).monoid.identity

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, identity)

    part_ref = data_ref
    if scratch:  # non-f32 data: the reduction runs on an f32 copy
        part_ref, = scratch
        part_ref[...] = data_ref[...].astype(jnp.float32)
    group_reduce_scatter(seg_ref, part_ref, out_ref, group_size, strategy,
                         op=op)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "tile", "group_size", "strategy",
                     "op", "interpret"),
)
def segment_reduce(seg_ids, data, *, num_segments: int, tile: int = 256,
                   group_size: int = 32, strategy: str = "segment",
                   op: str = "add", interpret: bool | None = None):
    """seg_ids: (T,) non-decreasing; data: (T, C).  T may be ragged — both
    inputs are identity-extended to the next ``tile`` multiple (padding
    lanes target segment ``num_segments - 1`` with identity data).
    ``strategy`` is the name of any registered reduction strategy; ``op``
    names the reduction monoid ('add' / 'max' / 'min').  ``interpret``
    defaults to the backend's answer (``common.pallas_call``)."""
    if tile % group_size:
        raise ValueError(f"tile={tile} not a multiple of "
                         f"group_size={group_size}")
    monoid = get_strategy(strategy, op=op).monoid
    t, c = data.shape
    t_pad = round_up(max(t, 1), tile)
    if t_pad != t:
        pad = t_pad - t
        seg_ids = jnp.concatenate(
            [seg_ids, jnp.full((pad,), num_segments - 1, seg_ids.dtype)])
        data = jnp.concatenate(
            [data, jnp.full((pad, c), monoid.identity, data.dtype)])
    grid = (1, t_pad // tile)
    scratch = ([] if data.dtype == jnp.float32
               else [pltpu.VMEM((tile, c), jnp.float32)])
    kernel = functools.partial(
        _segred_kernel, group_size=group_size, strategy=strategy, op=op)
    need = (vmem_bytes((tile, c), data.dtype, 2)
            + vmem_bytes((num_segments, c), jnp.float32, block_buffers(1))
            + sum(vmem_bytes((tile, c), jnp.float32) for _ in scratch))
    return pallas_call(
        kernel,
        name="segment_reduce",
        grid=grid,
        in_specs=[
            # segment ids as (1, tile) lane rows in SMEM: the writeback
            # targets are scalar reads
            pl.BlockSpec((None, 1, tile), lambda j, i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tile, c), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((num_segments, c), lambda j, i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_segments, c), jnp.float32),
        scratch_shapes=scratch,
        vmem_need=need,
        interpret=interpret,
    )(seg_ids.reshape(-1, 1, tile), data)
