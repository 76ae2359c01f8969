"""Plain float32 reference of full-batch GAT training, its control and its
planted faults.

Straight ``jax.numpy`` over the graph's COO entries: per head the score
``LeakyReLU(a_l·Wh_i + a_r·Wh_j)``, the softmax through
``jax.ops.segment_max`` and ``segment_sum``, the coefficient-weighted sum
of ``Wh_j`` by ``segment_sum``; dense products at matmul precision
``highest``; gradients by ``jax.value_and_grad``; Adam as Kingma and Ba's
Algorithm 1 (``bench.reference``).  It imports nothing of the program:
the graph, features, labels, weights and dropout masks come from the
benchmark's own generators (``bench.traffic``).

The control is the same reference at matmul precision ``default``, one
bfloat16 pass, below the configuration's ``highest`` (on the chip; the
CPU computes ``default`` in float32, so the tests run :func:`dot_bf16`,
the same pass written out).  The faults
(:data:`FAULTS`) are the reference with a part of the model left out or
changed: the coefficient dropout mask dropped, the score without its
LeakyReLU, the output layer's heads concatenated where they are
averaged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import to_host
from bench.traffic.gat import WEIGHTS, masks
from bench.traffic.gcn import step_key

#: planted faults: keyword arguments of :func:`make_train_step`
FAULTS = {
    "no_coef_mask": {"coef_masks": False},
    "no_leaky_relu": {"leaky": False},
    "concat_heads": {"concat_out": True},
}


def dot_bf16(a, b):
    """``a @ b`` in one bfloat16 pass: each operand rounded to bfloat16,
    the products summed in float32 (what precision ``default`` computes on
    the chip)."""
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def layer(rows, cols, n, h, w, a_l, a_r, b, *, concat, slope, leaky=True,
          input_keep=None, coef_keep=None, dot=jnp.matmul):
    """One GAT layer: h (n, F_in), w (F_in, H·F), a_l/a_r (H, F)."""
    n_heads, width = a_l.shape
    if input_keep is not None:
        h = h * input_keep
    wh = dot(h, w).reshape(n, n_heads, width)
    s = jnp.sum(wh * a_l, axis=-1)
    t = jnp.sum(wh * a_r, axis=-1)
    e = s[rows] + t[cols]
    if leaky:
        e = jnp.where(e > 0, e, slope * e)
    m = jax.ops.segment_max(e, rows, num_segments=n)
    p = jnp.exp(e - m[rows])
    coef = p / jax.ops.segment_sum(p, rows, num_segments=n)[rows]
    if coef_keep is not None:
        coef = coef * coef_keep
    out = jax.ops.segment_sum(coef[..., None] * wh[cols], rows, num_segments=n)
    if concat:  # b is (H·F,), or (F,) for each head under concat_heads
        return (out + b.reshape(-1, width)).reshape(n, -1)
    return jnp.mean(out, axis=1) + b


def forward(cfg, p, x, rows, cols, keeps=None, *, leaky=True,
            concat_out=False, dot=jnp.matmul):
    """The logits: a concatenating layer with ELU, then the output layer
    (averaged, or concatenated under the fault ``concat_out``)."""
    keeps = keeps or {}
    n = cfg["n_nodes"]
    h = jax.nn.elu(layer(rows, cols, n, x, p["w0"], p["al0"], p["ar0"],
                         p["b0"], concat=True, slope=cfg["slope"], leaky=leaky,
                         input_keep=keeps.get("x0"),
                         coef_keep=keeps.get("coef0"), dot=dot))
    return layer(rows, cols, n, h, p["w1"], p["al1"], p["ar1"], p["b1"],
                 concat=concat_out, slope=cfg["slope"], leaky=leaky,
                 input_keep=keeps.get("x1"), coef_keep=keeps.get("coef1"),
                 dot=dot)


def make_train_step(cfg: dict, graph: dict, *, precision: str = "highest",
                    dot=jnp.matmul, coef_masks: bool = True,
                    leaky: bool = True, concat_out: bool = False):
    """One jitted reference training step,
    ``(params, m, v, t, x, y, train, key) -> (params, m, v, loss, grads)``,
    its projections ``dot`` at matmul ``precision``, with the faults of
    :data:`FAULTS` as options."""
    wd = cfg["weight_decay"]
    lr, b1, b2, eps = cfg["lr"], cfg["adam_b1"], cfg["adam_b2"], cfg["adam_eps"]
    rows, cols = jnp.asarray(graph["rows"]), jnp.asarray(graph["indices"])
    nnz = int(rows.shape[0])

    def loss_fn(p, x, y, train, key):
        keeps = masks(key, cfg, nnz)
        if not coef_masks:
            keeps = {k: v for k, v in keeps.items() if not k.startswith("coef")}
        logits = forward(cfg, p, x, rows, cols, keeps, leaky=leaky,
                         concat_out=concat_out, dot=dot)
        logp = jax.nn.log_softmax(logits[train], axis=-1)
        nll = -jnp.mean(jnp.take_along_axis(logp, y[train][:, None], axis=1))
        return nll + 0.5 * wd * sum(jnp.sum(p[k] ** 2) for k in WEIGHTS)

    @jax.jit
    def step(p, m, v, t, x, y, train, key):
        with jax.default_matmul_precision(precision):
            loss, g = jax.value_and_grad(loss_fn)(p, x, y, train,
                                                  step_key(key, t))
        t1 = t + 1
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1 = 1 - b1 ** t1.astype(jnp.float32)
        c2 = 1 - b2 ** t1.astype(jnp.float32)
        p = jax.tree.map(lambda p_, m_, v_: p_ - lr * (m_ / c1)
                         / (jnp.sqrt(v_ / c2) + eps), p, m, v)
        return p, m, v, loss, g

    return step


def first_steps(cfg: dict, graph: dict, inputs: dict, *, steps: int = 3,
                step=None, follow: dict | None = None, **variant) -> dict:
    """The reference's first ``steps`` steps from the run's initial
    parameters, as ``bench.reference.first_steps`` returns them, with the
    parameters before each step and after the last under ``params``.

    With ``follow``, the snapshot of the run under test, each step starts
    from that run's parameters before it, and the reference carries its
    own moments: its losses are those of the same points, and its
    ``params_end`` is the initial parameters plus the sum of its own
    steps' changes.  Two runs left to go their own ways part by more than
    rounding: Adam's first step is the sign of each gradient element, so
    an element whose gradient rounding puts on either side of nought moves
    by twice the learning rate, and the next losses follow (PERF.md,
    Findings)."""
    step = step or make_train_step(cfg, graph, **variant)
    p = inputs["params"]
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    snap = {"losses": [], "params0": to_host(p), "params": [to_host(p)]}
    end = dict(snap["params0"])
    for t in range(steps):
        if follow is not None:
            p = {k: jnp.asarray(a, jnp.float32)
                 for k, a in follow["params"][t].items()}
        before = to_host(p)
        p, m, v, loss, g = step(p, m, v, jnp.int32(t), inputs["x"], inputs["y"],
                                inputs["train"], inputs["dropout_key"])
        after = to_host(p)
        snap["losses"].append(float(loss))
        snap["params"].append(after)
        end = {k: end[k] + (after[k] - before[k]) for k in end}
        if t == 0:
            snap["grad1"], snap["params1"] = to_host(g), after
    snap["params_end"] = end
    return snap
