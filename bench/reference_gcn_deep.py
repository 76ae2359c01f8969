"""Plain float32 reference of full-batch training of OGB's L-layer GCN
(a batch norm, ReLU and dropout after each hidden layer), its control and
its planted faults.

Straight ``jax.numpy`` over the graph's COO entries: each layer
``A (H W) + b`` with the propagation a gather, a scale and a segment sum
(``bench.traffic.gcn.propagate``); after each hidden layer the batch
norm with its batch statistics (biased variance, eps and momentum of the
configuration; the running statistics moved to the batch mean and the
unbiased variance, as ``torch.nn.BatchNorm1d`` keeps them), ReLU and the
step's dropout mask; the mean negative log-likelihood over the training
nodes; gradients by ``jax.value_and_grad``; Adam as Kingma and Ba's
Algorithm 1 (``bench.reference``).  Dense products at matmul precision
``highest``.  It imports nothing of the program: the graph, features,
labels, weights and masks come from the benchmark's own generators
(``bench.traffic``), and each step starts from the program's parameters
before it where it follows a run (``bench.reference_gat.first_steps``,
whose reason holds here too).

The control is the same reference at matmul precision ``default``, one
bfloat16 pass, below the configuration's ``highest`` (on the chip; the
CPU computes ``default`` in float32, so the tests run
``bench.reference_gat.dot_bf16``, the same pass written out).  The faults
(:data:`FAULTS`) are the reference with half of the training nodes left
out of the loss, and with the logits altered where they are produced.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference_gat import first_steps as _first_steps
from bench.traffic.gcn import propagate, step_key
from bench.traffic.gcn_deep import masks

#: planted faults: keyword arguments of :func:`make_train_step`
FAULTS = {
    "half_batch": {"labelled": slice(None, None, 2)},
    "altered_logits": {"tamper": lambda logits: logits.at[:, 0].add(0.05)},
}


def forward(cfg, p, stats, x, rows, cols, vals, keeps=None, *, dot=jnp.matmul):
    """``(logits, stats')``: the L layers over the COO entries, each hidden
    one followed by the batch norm, ReLU and its mask ``keep<i>``."""
    n, eps, m = cfg["n_nodes"], cfg["norm_eps"], cfg["norm_momentum"]
    keeps = keeps or {}
    out = {}
    h = x
    for i in range(cfg["layers"]):
        z = propagate(rows, cols, vals, dot(h, p[f"w{i}"]), n) + p[f"b{i}"]
        if i == cfg["layers"] - 1:
            return z, out
        mu = jnp.mean(z, axis=0)
        var = jnp.mean((z - mu) ** 2, axis=0)
        out[f"mean{i}"] = (1 - m) * stats[f"mean{i}"] + m * mu
        out[f"var{i}"] = (1 - m) * stats[f"var{i}"] + m * var * n / (n - 1)
        z = (z - mu) / jnp.sqrt(var + eps) * p[f"gamma{i}"] + p[f"beta{i}"]
        h = jnp.maximum(z, 0.0)
        if keeps.get(f"keep{i}") is not None:
            h = h * keeps[f"keep{i}"]
    raise ValueError("a GCN needs a layer")


def make_train_step(cfg: dict, graph: dict, *, precision: str = "highest",
                    dot=jnp.matmul, labelled: slice = slice(None),
                    tamper=None):
    """One jitted reference training step,
    ``(params, m, v, t, x, y, train, key) -> (params, m, v, loss, grads)``,
    its dense products ``dot`` at matmul ``precision``; ``labelled`` picks
    which training nodes enter the loss and ``tamper`` alters the logits
    where they are produced (:data:`FAULTS`).  The batch norms' running
    statistics do not enter the loss in training and are left out."""
    lr, b1, b2, eps = cfg["lr"], cfg["adam_b1"], cfg["adam_b2"], cfg["adam_eps"]
    coo = tuple(jnp.asarray(graph[k]) for k in ("rows", "indices", "vals"))
    fresh = {f"{k}{i}": jnp.full((cfg["hidden"],), 1.0 if k == "var" else 0.0)
             for i in range(cfg["layers"] - 1) for k in ("mean", "var")}

    def loss_fn(p, x, y, train, key):
        logits, _ = forward(cfg, p, fresh, x, *coo, masks(key, cfg), dot=dot)
        if tamper is not None:
            logits = tamper(logits)
        idx = train[labelled]
        logp = jax.nn.log_softmax(logits[idx], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[idx][:, None], axis=1))

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
    """The reference's first ``steps`` steps, following the run ``follow``
    where given: ``bench.reference_gat.first_steps`` over
    :func:`make_train_step`."""
    step = step or make_train_step(cfg, graph, **variant)
    return _first_steps(cfg, graph, inputs, steps=steps, step=step,
                        follow=follow)

