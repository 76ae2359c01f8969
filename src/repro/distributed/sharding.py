"""Sharding rules: logical param/activation axes -> mesh axes.

Mesh axes: ``("pod", "data", "model")`` multi-pod or ``("data", "model")``
single-pod. Policy (Megatron-style TP + DP, see DESIGN.md §5):

* attention qkv projections column-parallel, output row-parallel on
  ``model``;
* MLP wi/wg column-, wo row-parallel on ``model``;
* MoE experts expert-parallel on ``model`` (E dim);
* mamba in/out projections row-parallel on ``model`` (contraction dim);
* embeddings vocab-sharded on ``model`` when divisible, else replicated
  (mamba2 50280 / hymba 32001 / whisper 51866 are not 16-divisible);
* norms / scalars replicated;
* batch over ``(pod, data)``; decode KV caches shard *sequence* over
  ``model`` (online-softmax combines become small all-reduces);
* any proposed sharded dim that does not divide its mesh axis falls back
  to replication for that dim.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

MODEL_AXIS = "model"


def data_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _fit(mesh, spec: P, shape) -> P:
    """Drop sharding on dims that don't divide the assigned axis size."""
    fixed = []
    for dim, axes in enumerate(spec):
        if axes is None:
            fixed.append(None)
            continue
        ax_tuple = axes if isinstance(axes, tuple) else (axes,)
        size = 1
        for a in ax_tuple:
            size *= mesh.shape[a]
        fixed.append(axes if shape[dim] % size == 0 else None)
    return P(*fixed)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


DATA = "__data__"  # sentinel resolved to the mesh's data axes


def _param_spec(path: str, ndim: int) -> P:
    """Logical rule table. Layer params carry a leading stacked-L dim, so
    rules address the trailing dims and we left-pad with None."""

    def pad(spec_tail):
        return P(*([None] * (ndim - len(spec_tail)) + list(spec_tail)))

    if path.endswith("embed"):
        return pad([MODEL_AXIS, None])
    if "router" in path:
        return pad([None, None])
    # MoE experts: (E, D, F) / (E, F, D)
    if any(f"moe/{n}" in path for n in ("wg", "wi", "wo")):
        return pad([MODEL_AXIS, None, None])
    # Attention: replicated over model at baseline (no assigned arch has
    # 16-divisible kv heads; partial head sharding makes GSPMD all-reduce
    # the score tensors — measured 22 GB/layer on qwen2). The weights are
    # FSDP-sharded over the data axes (d_model dim) so the 33B dense
    # models fit HBM; XLA inserts the per-layer all-gather. Seq-parallel
    # attention is ``ModelConfig.seq_parallel_attn``.
    if "attn/" in path:
        if path.endswith("/w"):
            return pad([DATA, None])
        return P(*([None] * ndim))
    # MLP projections (bare kernels, no bias sub-dict)
    if path.endswith(("wi", "wg")):
        return pad([None, MODEL_AXIS])
    if path.endswith("wo"):
        return pad([MODEL_AXIS, None])
    # mamba mixer (split projections; the Mamba-2 TP scheme)
    if path.endswith(("z_proj", "x_proj")):
        return pad([None, MODEL_AXIS])
    if path.endswith("dt_proj"):
        return pad([None, MODEL_AXIS])  # H dim; dropped when indivisible
    if path.endswith("bc_proj"):
        return P(*([None] * ndim))
    if path.endswith(("conv_x_w",)):
        return pad([None, MODEL_AXIS])
    if path.endswith(("conv_x_b",)):
        return pad([MODEL_AXIS])
    if "mixer" in path and path.endswith("norm"):
        return pad([MODEL_AXIS])
    if path.endswith(("A_log", "D", "dt_bias")):
        return pad([MODEL_AXIS])
    if path.endswith("out_proj"):
        return pad([MODEL_AXIS, None])
    # everything else (norms, conv_bc, betas): replicated
    return P(*([None] * ndim))


def param_shardings(mesh, params_shape):
    """params_shape: pytree of ShapeDtypeStruct (from jax.eval_shape)."""
    dp = data_axes(mesh)

    def rule(path, leaf):
        spec = _param_spec(_path_str(path), len(leaf.shape))
        spec = P(*[dp if a == DATA else a for a in spec])
        return NamedSharding(mesh, _fit(mesh, spec, leaf.shape))

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def cache_shardings(mesh, cfg, cache_shape):
    """Serve-cache shardings. KV caches (L, B, S, K, dh): batch over data
    axes, sequence over model. SSM state (L, B, H, N, P): heads over model.
    Cross-attn caches (L, B, 1500, K, dh): head_dim over model (1500 and
    K=20 don't divide 16). Conv state: channel over model."""
    dp = data_axes(mesh)

    def rule(path, leaf):
        name = _path_str(path)
        nd = len(leaf.shape)
        if name.endswith("pos"):
            spec = P()
        elif name in ("k", "v"):
            spec = P(None, dp, MODEL_AXIS, None, None)
        elif name in ("ck", "cv"):
            spec = P(None, dp, None, None, MODEL_AXIS)
        elif "ssm" in name:
            spec = P(*([None, dp, MODEL_AXIS, None, None][:nd]))
        elif "conv" in name:
            spec = P(*([None, dp, None, MODEL_AXIS][:nd]))
        else:
            spec = P(*([None] * nd))
        return NamedSharding(mesh, _fit(mesh, spec, leaf.shape))

    return jax.tree_util.tree_map_with_path(rule, cache_shape)
