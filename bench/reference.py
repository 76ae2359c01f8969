"""Plain float32 reference of full-batch GCN training, and its control.

Straight ``jax.numpy``: the propagation is a gather, a scale and a
segment sum over the graph's COO entries; the dense products run at
``Precision.HIGHEST``; the gradients come from ``jax.value_and_grad``;
Adam is written out as Kingma and Ba's Algorithm 1.  It imports nothing
of the program and takes nothing the program made: the graph, features,
labels, initial weights and dropout masks come from the benchmark's own
generators (``bench.traffic.gcn``).

The control is the same reference with every dense product at the
precision one step below the configuration's ``highest``: ``high``,
three bfloat16 passes (:func:`dot_high`, XLA's own, on the chip; the
CPU has no such pass, so the tests run :func:`dot_bf16x3`, the same
three passes written out).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic.gcn import dropout, propagate, step_key

HIGHEST = jax.lax.Precision.HIGHEST


def dot_highest(a, b):
    """``a @ b`` in float32 at full precision."""
    return jnp.matmul(a, b, precision=HIGHEST)


def _bf16(a):
    """``a`` cut to its bfloat16 head by truncating the low 16 bits of each
    float32, as the chip splits operands for ``high`` (bit operations,
    so that no backend may skip or round them)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _dot3(a, b):
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    return dot_highest(a_hi, b_hi) + (dot_highest(a_hi, b_lo)
                                      + dot_highest(a_lo, b_hi))


@jax.custom_vjp
def dot_bf16x3(a, b):
    """``a @ b`` in three bfloat16 passes, forward and backward: each
    operand split into a truncated bfloat16 head and the bfloat16 head of
    the rest, three of the four products summed in float32.  What the
    chip's precision ``high`` computes, written out so that the CPU
    computes it too."""
    return _dot3(a, b)


def _dot3_fwd(a, b):
    return _dot3(a, b), (a, b)


def _dot3_bwd(res, g):
    a, b = res
    return _dot3(g, b.T), _dot3(a.T, g)


dot_bf16x3.defvjp(_dot3_fwd, _dot3_bwd)


def dot_high(a, b):
    """``a @ b`` at XLA's own precision ``high`` (three bfloat16 passes on
    a TPU; the CPU computes it in full float32)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)


def make_train_step(cfg: dict, graph: dict, *, dot=dot_highest,
                    labelled: slice = slice(None), tamper=None, reset=None):
    """One jitted reference training step,
    ``(params, m, v, t, x, y, train, key) -> (params, m, v, loss, grads)``.

    ``labelled`` picks which of the labelled nodes enter the loss,
    ``tamper`` alters the logits where they are produced, and ``reset``
    drops what the optimizer carries from step to step (``"moments"``:
    ``m`` and ``v`` start from nought in every step; ``"all"``: the step
    count too, and with it the dropout mask): the readings plant faults
    through them."""
    n = cfg["n_nodes"]
    rate, wd = cfg["input_dropout"], cfg["weight_decay"]
    lr, b1, b2, eps = cfg["lr"], cfg["adam_b1"], cfg["adam_b2"], cfg["adam_eps"]

    def forward(p, x, rows, cols, vals):
        z = propagate(rows, cols, vals, dot(x, p["w0"]), n) + p["b0"]
        h = jnp.maximum(z, 0.0)
        return propagate(rows, cols, vals, dot(h, p["w1"]), n) + p["b1"]

    def loss_fn(p, x, y, train, key, rows, cols, vals):
        logits = forward(p, dropout(x, key, rate), rows, cols, vals)
        if tamper is not None:
            logits = tamper(logits)
        idx = train[labelled]
        logp = jax.nn.log_softmax(logits[idx], axis=-1)
        nll = -jnp.mean(jnp.take_along_axis(logp, y[idx][:, None], axis=1))
        return nll + 0.5 * wd * jnp.sum(p["w0"] ** 2)

    @jax.jit
    def step(p, m, v, t, x, y, train, key, rows, cols, vals):
        if reset is not None:
            m, v = (jax.tree.map(jnp.zeros_like, a) for a in (m, v))
        if reset == "all":
            t = jnp.zeros_like(t)
        loss, g = jax.value_and_grad(loss_fn)(p, x, y, train, step_key(key, t),
                                              rows, cols, vals)
        t1 = t + 1
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1 = 1 - b1 ** t1.astype(jnp.float32)
        c2 = 1 - b2 ** t1.astype(jnp.float32)
        p = jax.tree.map(lambda p_, m_, v_: p_ - lr * (m_ / c1)
                         / (jnp.sqrt(v_ / c2) + eps), p, m, v)
        return p, m, v, loss, g

    coo = tuple(jnp.asarray(graph[k]) for k in ("rows", "indices", "vals"))

    def run(p, m, v, t, x, y, train, key):
        return step(p, m, v, t, x, y, train, key, *coo)

    return run


def first_steps(cfg: dict, graph: dict, inputs: dict, *, steps: int = 3,
                step=None, **variant) -> dict:
    """The reference's first ``steps`` training steps from the run's
    initial parameters: each step's loss, the first gradient, and the
    parameters before, after the first step and at the end.  ``step`` is a step of
    :func:`make_train_step`, made here with ``variant`` where not given."""
    step = step or make_train_step(cfg, graph, **variant)
    p = inputs["params"]
    p0 = to_host(p)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    snap = {"losses": [], "params0": p0}
    for t in range(steps):
        p, m, v, loss, g = step(p, m, v, jnp.int32(t), inputs["x"], inputs["y"],
                                inputs["train"], inputs["dropout_key"])
        snap["losses"].append(float(loss))
        if t == 0:
            snap["grad1"], snap["params1"] = to_host(g), to_host(p)
    snap["params_end"] = to_host(p)
    return snap


def to_host(tree) -> dict:
    """A parameter dict copied to the host as float64 NumPy arrays."""
    return {k: np.asarray(v, np.float64) for k, v in tree.items()}
