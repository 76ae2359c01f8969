"""Plan execution — route each :class:`~repro.fuse.ir.Launch` through
the library surface that realizes it.

``run_plan`` threads the chain value through the launches: ``spmm``
anchors go through ``repro.sparse.spmm`` (the differentiable scheduled
kernel, the launch's merged epilogue attached), ``grouped_matmul``
anchors through ``kernels.ops.grouped_matmul`` (differentiable,
epilogued), ``segment_reduce`` through ``repro.sparse.segment_reduce``,
``combine`` through the jnp monoid scatter (:func:`moe_combine` — kept
in XLA for differentiability), ``norm`` launches through
:func:`batch_norm` in XLA, and unfused ``ewise`` launches apply
their epilogue spec in XLA.  Because every Pallas path already carries a
custom VJP, a planned chain is differentiable end to end.

``run_chain_ref`` is the parity oracle: the *unfused spec composition*,
each node executed separately through the pure-jnp references — what
every plan of the same chain must match within dtype tolerance.

Operands travel in ``params`` — a per-chain-node list of dicts (aligned
with the chain; see the builders in ``repro.fuse.ir``):

=================  =======================================================
node kind          recognized params keys
=================  =======================================================
spmm               ``a`` (CSR/GroupedCOO/ELL), optional ``w`` (dense
                   weight: the launch computes ``A @ (x @ w)``)
grouped_matmul     ``tile_experts``, ``weights``, optional ``token_tile``
                   / ``f_tile`` / ``d_tile``
segment_reduce     ``seg_ids``, ``num_segments``
combine            ``topi``, ``topv``, ``num_tokens``
ewise              ``bias`` / ``residual`` arrays for its epilogue flags
norm               ``gamma``, ``beta``, running ``mean`` / ``var``, and
                   optionally ``keep`` (a dropout mask after the
                   activation)
=================  =======================================================
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .ir import FusePlan, Launch

__all__ = ["batch_norm", "moe_combine", "run_chain_ref", "run_plan"]


def moe_combine(y, topi, topv, num_tokens: int, op: str = "sum"):
    """Gate-weighted expert→token combine under the named monoid.

    ``y`` (S, D) routed-slot outputs, ``topi`` (S,) destination token of
    each slot, ``topv`` (S,) gate weight.  'sum' is the standard MoE
    combine; 'min' takes the elementwise min over a token's routed
    experts (untouched tokens → 0, matching sum's zero-init); 'mean'
    averages over the routed experts.  Pure jnp scatters — the combine
    stays differentiable in ``y`` and ``topv``."""
    d = y.shape[-1]
    y = y.astype(jnp.float32) * topv[:, None].astype(jnp.float32)
    flat_i = topi.reshape(-1)
    if op == "sum":
        return jnp.zeros((num_tokens, d), jnp.float32).at[flat_i].add(y)
    if op == "min":
        out = jnp.full((num_tokens, d), jnp.inf,
                       jnp.float32).at[flat_i].min(y)
        return jnp.where(jnp.isinf(out), 0.0, out)
    if op == "mean":
        tot = jnp.zeros((num_tokens, d), jnp.float32).at[flat_i].add(y)
        cnt = jnp.zeros((num_tokens, 1), jnp.float32).at[flat_i].add(
            jnp.ones((y.shape[0], 1), jnp.float32))
        return tot / jnp.maximum(cnt, 1.0)
    raise ValueError(f"moe_combine op {op!r}; one of sum/min/mean")


def batch_norm(x, gamma, beta, mean, var, norm):
    """``(y, (mean', var'))``: ``x`` (rows, F) normalised under the
    :class:`~repro.fuse.ir.BatchNorm` ``norm`` (by its batch statistics
    in training, by the running ``mean`` / ``var`` in evaluation), then
    ``* gamma + beta``; and the running statistics it leaves (moved
    ``norm.momentum`` of the way to the batch mean and the unbiased
    batch variance in training, unchanged in evaluation).  Float32."""
    x = x.astype(jnp.float32)
    if norm.training:
        mu = jnp.mean(x, axis=0)
        v = jnp.mean(jnp.square(x - mu), axis=0)
        n = x.shape[0]
        m = norm.momentum
        running = jax.lax.stop_gradient(
            ((1 - m) * mean + m * mu,
             (1 - m) * var + m * v * (n / max(n - 1, 1))))
    else:
        mu, v, running = mean, var, (mean, var)
    return (x - mu) * jax.lax.rsqrt(v + norm.eps) * gamma + beta, running


def _run_norm(node, ep, cur, p, stats):
    """A norm launch: the node's batch norm (scope ``norm``), then its
    epilogue and dropout mask (scope ``act``), under the node's label;
    the running statistics go to ``stats[label]``."""
    with jax.named_scope(node.label or "norm"):
        with jax.named_scope("norm"):
            y, running = batch_norm(cur, p["gamma"], p["beta"], p["mean"],
                                    p["var"], node.norm)
        with jax.named_scope("act"):
            y = ep.apply(y)
            if p.get("keep") is not None:
                y = y * p["keep"]
    if stats is not None:
        stats[node.label] = running
    return y


def _ewise_bias(bias, params):
    """Bias operand of an *unfused* elementwise pass.  A 1-D feature
    bias broadcasts as (1, F); a 2-D per-expert (E, F) bias (the
    grouped_matmul operand the fused kernel indexes per tile via its
    expert map) is expanded to per-row (T, F) using the chain's routing
    params."""
    if bias is None:
        return None
    if bias.ndim == 1:
        return jnp.reshape(bias, (1, -1))
    for p in params:
        if p and p.get("tile_experts") is not None:
            return jnp.repeat(bias[p["tile_experts"]],
                              p.get("token_tile", 128), axis=0)
    return bias


def _epilogue_operands(launch: Launch, params):
    """Collect the launch epilogue's array operands from its members
    (whichever fused node declared the bias / residual supplies it)."""
    bias = residual = None
    for i in launch.members:
        p = params[i] or {}
        if p.get("bias") is not None:
            bias = p["bias"]
        if p.get("residual") is not None:
            residual = p["residual"]
    return bias, residual


def _run_launch(launch: Launch, cur, params, stats=None):
    a = launch.anchor
    p = params[launch.anchor_idx] or {}
    ep = launch.epilogue
    bias, residual = _epilogue_operands(launch, params)
    if a.kind == "norm":
        return _run_norm(a, ep, cur, p, stats)

    if a.kind == "spmm":
        from ..sparse import spmm

        x = cur if p.get("w") is None else cur @ p["w"]
        return spmm(p["a"], x, schedule=a.schedule or "auto",
                    bias=bias, residual=residual,
                    epilogue=None if ep.is_noop else ep)
    if a.kind == "grouped_matmul":
        from ..kernels.ops import grouped_matmul

        return grouped_matmul(
            cur, p["tile_experts"], p["weights"], bias=bias, epilogue=ep,
            token_tile=p.get("token_tile", 128),
            f_tile=p.get("f_tile", 128), d_tile=p.get("d_tile", 128))
    if a.kind == "segment_reduce":
        from ..sparse import segment_reduce

        return segment_reduce(p["seg_ids"], cur, p["num_segments"],
                              schedule=a.schedule, op=a.op)
    if a.kind == "combine":
        return moe_combine(cur, p["topi"], p["topv"], p["num_tokens"],
                           op=a.op)
    # unfused elementwise launch: the epilogue spec runs in XLA
    return ep.apply(cur, bias=_ewise_bias(bias, params),
                    residual=residual)


def run_plan(plan: FusePlan, x, params, stats=None):
    """Execute a plan: ``params`` is the per-chain-node operand list
    (``len(params) == len(plan.chain)``).  ``stats``, a dict, receives
    each norm launch's running statistics under its label."""
    assert len(params) == len(plan.chain), (len(params), len(plan.chain))
    cur = x
    for i, launch in enumerate(plan.launches):
        # the launch's device work, its backward too, carries this name
        with jax.named_scope(f"fuse.launch{i}.{launch.anchor.kind}"):
            cur = _run_launch(launch, cur, params, stats)
    return cur


def _run_node_ref(node, cur, p, params):
    """One node of the unfused spec composition (pure jnp / ref paths)."""
    p = p or {}
    if node.kind == "spmm":
        from ..kernels import ops as kops

        x = cur if p.get("w") is None else cur @ p["w"]
        out = kops.spmm(p["a"], x, impl="ref")
        return out if node.epilogue.is_noop else node.epilogue.apply(out)
    if node.kind == "grouped_matmul":
        from ..kernels.ops import grouped_matmul_ref

        return grouped_matmul_ref(cur, p["tile_experts"], p["weights"],
                                  epilogue=node.epilogue,
                                  token_tile=p.get("token_tile", 128))
    if node.kind == "segment_reduce":
        seg, n = p["seg_ids"], p["num_segments"]
        data = cur.astype(jnp.float32)
        if node.op == "sum":
            return jax.ops.segment_sum(data, seg, num_segments=n)
        if node.op == "max":
            return jax.ops.segment_max(data, seg, num_segments=n)
        if node.op == "min":
            return jax.ops.segment_min(data, seg, num_segments=n)
        tot = jax.ops.segment_sum(data, seg, num_segments=n)
        cnt = jax.ops.segment_sum(jnp.ones((data.shape[0], 1)), seg,
                                  num_segments=n)
        return tot / jnp.maximum(cnt, 1.0)
    if node.kind == "combine":
        return moe_combine(cur, p["topi"], p["topv"], p["num_tokens"],
                           op=node.op)
    if node.kind == "norm":
        return _run_norm(node, node.epilogue, cur, p, None)
    return node.epilogue.apply(cur, bias=_ewise_bias(p.get("bias"),
                                                     params),
                               residual=p.get("residual"))


def run_chain_ref(chain, x, params):
    """The unfused spec composition — every node its own pure-jnp pass.
    This is the oracle every plan of ``chain`` must match."""
    cur = x
    for node, p in zip(chain, params):
        cur = _run_node_ref(node, cur, p, params)
    return cur
