"""Inputs of a full-batch training job of OGB's L-layer GCN with a batch
norm between layers, made from a seed.

The graph is the GCN job's (``bench.traffic.gcn``): the seeded graph of
the configuration's counts, the same in every run.  The features and
teacher labels are made as there, over ``n_classes`` classes; the
training nodes are a seeded random draw of ``train_nodes`` of them (the
published split's size).  The weights are Glorot-uniform and the biases
zero, the norms' scales 1 and shifts 0, their running statistics 0 and
1.  The dropout masks of each step come from the run's dropout key and
the step count, on the device; the program's step and the reference draw
them through :func:`masks`, so both see the same masks.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import gcn


def widths(cfg: dict) -> list:
    """The width of each layer's input, then of the logits:
    ``[n_features, hidden, ..., hidden, n_classes]``."""
    return ([cfg["n_features"]] + [cfg["hidden"]] * (cfg["layers"] - 1)
            + [cfg["n_classes"]])


def make_inputs(cfg: dict, graph: dict, seed: int) -> dict:
    """Features, teacher labels, training nodes, initial parameters and
    running statistics, and the dropout key of one run."""
    import jax
    import jax.numpy as jnp

    n, f, c = cfg["n_nodes"], cfg["n_features"], cfg["n_classes"]
    k_x, k_t, k_p, k_drop, k_pick = jax.random.split(gcn.seed_key(seed), 5)

    @jax.jit
    def features_and_labels(k_x, k_t, rows, cols, vals):
        x = jax.random.normal(k_x, (n, f), jnp.float32)
        teacher = jax.random.normal(k_t, (f, c), jnp.float32)
        xt = jnp.matmul(x, teacher, precision=jax.lax.Precision.HIGHEST)
        return x, jnp.argmax(gcn.propagate(rows, cols, vals, xt, n), axis=-1)

    x, y = features_and_labels(k_x, k_t, jnp.asarray(graph["rows"]),
                               jnp.asarray(graph["indices"]),
                               jnp.asarray(graph["vals"]))
    rng = np.random.default_rng(np.asarray(k_pick).tolist())
    train = np.sort(rng.permutation(n)[:cfg["train_nodes"]])
    params, stats = init_state(k_p, cfg)
    return {"x": x, "y": y, "train": jnp.asarray(train, jnp.int32),
            "params": params, "stats": stats, "dropout_key": k_drop}


def init_state(key, cfg: dict) -> tuple:
    """``(params, stats)``: each layer's Glorot-uniform ``w<i>`` and zero
    ``b<i>``; each hidden layer's norm ``gamma<i>`` 1 and ``beta<i>`` 0,
    and running ``mean<i>`` 0 and ``var<i>`` 1."""
    import jax
    import jax.numpy as jnp

    dims = widths(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(dims) - 1)
        params, stats = {}, {}
        for i, (k, fan_in, fan_out) in enumerate(zip(keys, dims, dims[1:])):
            lim = (6.0 / (fan_in + fan_out)) ** 0.5
            params[f"w{i}"] = jax.random.uniform(k, (fan_in, fan_out),
                                                 jnp.float32, -lim, lim)
            params[f"b{i}"] = jnp.zeros((fan_out,), jnp.float32)
            if i < len(dims) - 2:
                params[f"gamma{i}"] = jnp.ones((fan_out,), jnp.float32)
                params[f"beta{i}"] = jnp.zeros((fan_out,), jnp.float32)
                stats[f"mean{i}"] = jnp.zeros((fan_out,), jnp.float32)
                stats[f"var{i}"] = jnp.ones((fan_out,), jnp.float32)
        return params, stats

    return make(key)


def masks(key, cfg: dict) -> dict:
    """The dropout masks of one step: ``keep<i>`` (n_nodes, hidden) after
    each hidden layer, 0 or 1/(1-p)."""
    import jax
    import jax.numpy as jnp

    p = cfg["dropout"]
    keys = jax.random.split(key, cfg["layers"] - 1)
    return {f"keep{i}": jnp.where(
        jax.random.bernoulli(k, 1.0 - p, (cfg["n_nodes"], cfg["hidden"])),
        jnp.float32(1.0 / (1.0 - p)), jnp.float32(0.0))
        for i, k in enumerate(keys)}
