"""Inputs of a full-batch GAT training job, made from a seed.

The graph, features, labels and labelled nodes are the GCN job's
(``bench.traffic.gcn``): the same seeded graph of the configuration's
counts, of which GAT uses the pattern alone.  The weights are GAT's,
Glorot-uniform from the run's seed; the dropout masks of each step come
from the run's dropout key and the step count, on the device.  The
program's step and the reference draw them through :func:`masks`, so
both see the same masks.
"""
from __future__ import annotations

from bench.traffic import gcn

#: the weights the L2 term covers (every parameter but the biases)
WEIGHTS = ("w0", "al0", "ar0", "w1", "al1", "ar1")


def param_shapes(cfg: dict) -> dict:
    """Each parameter's shape: the first layer's ``heads`` heads of
    ``hidden`` features, the output layer's ``out_heads`` heads of
    ``n_classes``."""
    f, c = cfg["n_features"], cfg["n_classes"]
    h0, w0, h1 = cfg["heads"], cfg["hidden"], cfg["out_heads"]
    return {"w0": (f, h0 * w0), "al0": (h0, w0), "ar0": (h0, w0),
            "b0": (h0 * w0,), "w1": (h0 * w0, h1 * c), "al1": (h1, c),
            "ar1": (h1, c), "b1": (c,)}


def make_inputs(cfg: dict, graph: dict, seed: int) -> dict:
    """The GCN job's features, labels, labelled nodes and dropout key of
    ``seed`` (:func:`bench.traffic.gcn.make_inputs`), with GAT's initial
    parameters from the same parameter key."""
    import jax

    inputs = gcn.make_inputs(cfg, graph, seed)
    k_p = jax.random.split(gcn.seed_key(seed), 5)[2]
    return {**inputs, "params": init_params(k_p, cfg)}


def init_params(key, cfg: dict) -> dict:
    """Glorot-uniform weights and zero biases, in one jitted call, with
    the published code's fans: each head's W is (F_in, F) and each of a_l
    and a_r an (F, 1) projection of its own."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(cfg)

    @jax.jit
    def make(key):
        keys = dict(zip(shapes, jax.random.split(key, len(shapes))))
        out = {}
        for name, shape in shapes.items():
            if name.startswith("b"):
                out[name] = jnp.zeros(shape, jnp.float32)
                continue
            heads = shapes["al" + name[1:]][0] if name.startswith("w") else 1
            fan = (shape[0] + shape[1] // heads if name.startswith("w")
                   else shape[1] + 1)
            lim = (6.0 / fan) ** 0.5
            out[name] = jax.random.uniform(keys[name], shape, jnp.float32,
                                           -lim, lim)
        return out

    return make(key)


def masks(key, cfg: dict, nnz: int) -> dict:
    """The dropout masks of one step, 0 or 1/(1-p): ``x0`` on the
    features, ``coef0`` on the first layer's (nnz, heads) coefficients,
    ``x1`` on the first layer's output, ``coef1`` on the output layer's
    coefficients."""
    import jax
    import jax.numpy as jnp

    n, p_in, p_coef = cfg["n_nodes"], cfg["input_dropout"], cfg["coef_dropout"]
    shapes = {"x0": ((n, cfg["n_features"]), p_in),
              "coef0": ((nnz, cfg["heads"]), p_coef),
              "x1": ((n, cfg["heads"] * cfg["hidden"]), p_in),
              "coef1": ((nnz, cfg["out_heads"]), p_coef)}
    keys = dict(zip(shapes, jax.random.split(key, len(shapes))))
    return {k: jnp.where(jax.random.bernoulli(keys[k], 1.0 - p, shape),
                         jnp.float32(1.0 / (1.0 - p)), jnp.float32(0.0))
            for k, (shape, p) in shapes.items()}
