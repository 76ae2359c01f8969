"""Plain float32 reference of the two-layer GAT (Veličković et al., ICLR
2018, arXiv:1710.10903): the oracle ``models.layers.gat_two_layer`` is
tested against.

Straight ``jax.numpy`` over the pattern's entry list: per head the
scores ``LeakyReLU(a_l·Wh_i + a_r·Wh_j)``, a softmax through
``jax.ops.segment_max`` and ``segment_sum``, and the coefficient-weighted
sum of ``Wh_j``; dense products at ``Precision.HIGHEST``; gradients by
``jax.grad``.  No kernels, schedules or custom VJPs.  Departures from the
published model are the configuration's to record (``bench/configs``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: the weights L2 applies to (every parameter but the biases)
WEIGHTS = ("w0", "al0", "ar0", "w1", "al1", "ar1")


def attention_layer(rows, cols, n, h, w, a_l, a_r, b, *, concat, slope,
                    input_keep=None, coef_keep=None):
    """One GAT layer; ``h`` (n, F_in), ``w`` (F_in, H·F), ``a_l``/``a_r``
    (H, F); heads concatenated or averaged, then the bias."""
    n_heads, width = a_l.shape
    if input_keep is not None:
        h = h * input_keep
    wh = jnp.matmul(h, w, precision=jax.lax.Precision.HIGHEST)
    wh = wh.reshape(n, n_heads, width)
    s = jnp.sum(wh * a_l, axis=-1)  # (n, H)
    t = jnp.sum(wh * a_r, axis=-1)
    pre = s[rows] + t[cols]  # (nnz, H)
    e = jnp.where(pre > 0, pre, slope * pre)
    m = jax.ops.segment_max(e, rows, num_segments=n)
    p = jnp.exp(e - m[rows])
    coef = p / jax.ops.segment_sum(p, rows, num_segments=n)[rows]
    if coef_keep is not None:
        coef = coef * coef_keep
    out = jax.ops.segment_sum(coef[..., None] * wh[cols], rows, num_segments=n)
    out = out.reshape(n, n_heads * width) if concat else jnp.mean(out, axis=1)
    return out + b


def forward(params, x, rows, cols, n, *, slope=0.2, keeps=None):
    """Logits (n, C) of the two-layer GAT: a concatenating layer with
    ELU, then an averaging output layer.  ``keeps`` as in
    ``gat_two_layer``."""
    keeps = keeps or {}
    h = x
    for i, concat in enumerate((True, False)):
        h = attention_layer(rows, cols, n, h, params[f"w{i}"],
                            params[f"al{i}"], params[f"ar{i}"],
                            params[f"b{i}"], concat=concat, slope=slope,
                            input_keep=keeps.get(f"x{i}"),
                            coef_keep=keeps.get(f"coef{i}"))
        if concat:
            h = jax.nn.elu(h)
    return h


def loss(params, x, y, train, rows, cols, n, *, slope=0.2, weight_decay=0.0,
         keeps=None):
    """Mean cross-entropy over the labelled nodes ``train`` plus
    ``weight_decay · Σ ½‖w‖²`` over :data:`WEIGHTS`."""
    logits = forward(params, x, rows, cols, n, slope=slope, keeps=keeps)
    logp = jax.nn.log_softmax(logits[train], axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, y[train][:, None], axis=1))
    return nll + 0.5 * weight_decay * sum(jnp.sum(params[k] ** 2)
                                          for k in WEIGHTS)
