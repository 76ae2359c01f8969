"""The reduction mesh.

A function, not a module-level constant, so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax


def make_reduction_mesh(axis_size: int | None = None, *,
                        axis: str = "shards"):
    """1-D mesh for the distributed reduction collectives (DESIGN.md §12:
    ``repro.sparse.dist_spmm`` / ``dist_attention_shard_map`` and the
    distributed tuner) over this process's devices — the chips of a TPU
    host, or the forced host devices of
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` in tests."""
    n = len(jax.devices())
    if axis_size is None:
        axis_size = n
    if n % axis_size:
        raise ValueError(
            f"axis_size={axis_size} does not divide device count {n}")
    return jax.make_mesh((axis_size,), (axis,))
