"""Plain float32 reference of the L-layer GCN with a batch norm between
layers — OGB's full-batch GCN baseline (Hu et al., NeurIPS 2020,
arXiv:2005.00687; the model of Kipf and Welling, arXiv:1609.02907): the
oracle ``models.layers.gcn`` is tested against.

Each layer is ``Â (H W) + b``, the propagation a gather, a scale and a
``jax.ops.segment_sum`` over the adjacency's entries; after each hidden
layer a batch norm (batch statistics, biased variance, affine; the
running statistics moved ``momentum`` of the way to the batch mean and
the unbiased variance, as ``torch.nn.BatchNorm1d`` keeps them), ReLU,
and a dropout mask given from outside.  The loss is the mean negative
log-likelihood over the labelled nodes.  Dense products run under
``jax.default_matmul_precision("highest")``; gradients by ``jax.grad``.
No kernels, schedules, fusion or custom VJPs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5  # BatchNorm1d's eps
MOMENTUM = 0.1  # BatchNorm1d's momentum


def propagate(rows, cols, vals, h, n):
    """``Â h`` over the entries ``(rows, cols, vals)``: float32."""
    return jax.ops.segment_sum(vals[:, None] * h[cols], rows, num_segments=n)


def batch_norm(z, gamma, beta, mean, var, *, eps=EPS, momentum=MOMENTUM):
    """Training-mode batch norm of ``z`` (n, F): the normalised, scaled
    and shifted ``z`` and the moved running ``(mean, var)``."""
    mu = jnp.mean(z, axis=0)
    v = jnp.mean((z - mu) ** 2, axis=0)
    n = z.shape[0]
    running = ((1 - momentum) * mean + momentum * mu,
               (1 - momentum) * var + momentum * v * n / (n - 1))
    return (z - mu) / jnp.sqrt(v + eps) * gamma + beta, running


def forward(params, stats, x, rows, cols, vals, n, *, keeps=None, norm=True):
    """``(logits, stats')``: the L-layer GCN over ``params`` ``w<i>``,
    ``b<i>`` and, with ``norm``, ``gamma<i>``, ``beta<i>`` and the running
    ``stats`` ``mean<i>``, ``var<i>`` of each hidden layer; ``keeps``
    optional dropout masks ``keep<i>`` after each hidden ReLU."""
    keeps = keeps or {}
    n_layers = sum(1 for k in params if k.startswith("w"))
    out = {}
    h = x
    with jax.default_matmul_precision("highest"):
        for i in range(n_layers):
            z = propagate(rows, cols, vals, h @ params[f"w{i}"], n)
            if params.get(f"b{i}") is not None:
                z = z + params[f"b{i}"]
            if i == n_layers - 1:
                return z, out
            if norm:
                z, (out[f"mean{i}"], out[f"var{i}"]) = batch_norm(
                    z, params[f"gamma{i}"], params[f"beta{i}"],
                    stats[f"mean{i}"], stats[f"var{i}"])
            h = jnp.maximum(z, 0.0)
            if keeps.get(f"keep{i}") is not None:
                h = h * keeps[f"keep{i}"]
    raise ValueError("no layer")


def loss(params, stats, x, y, train, rows, cols, vals, n, *, keeps=None,
         norm=True):
    """``(nll, stats')``: the mean negative log-likelihood of the labels
    ``y`` over the nodes ``train``, and the running statistics."""
    logits, out = forward(params, stats, x, rows, cols, vals, n, keeps=keeps,
                          norm=norm)
    logp = jax.nn.log_softmax(logits[train], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[train][:, None], axis=1)), out
