"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real single CPU device.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh(
        (n // model_parallel, model_parallel), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


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
