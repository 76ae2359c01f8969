"""Inputs of a full-batch GCN training job, made from a seed.

The graph stands for a published dataset: it is drawn once per
configuration from the configuration's ``graph_seed``, with the
dataset's node and edge counts, so every run of a cell trains on the
same graph (as every run in the paper does) and the compiled step,
which holds the graph's formats, is found in the compile cache.  The
features, labels, training nodes, initial weights and dropout masks
come from the run's ``--seed``.

:func:`gcn_graph` is a copy of the program's ``gcn_graph_csr`` that
returns NumPy arrays, kept here so that the yardstick cannot move with
the program.
"""
from __future__ import annotations

import numpy as np

_WORD = 0xFFFFFFFF


def gcn_graph(n_nodes: int, n_edges: int, *, alpha: float = 0.5,
              seed: int = 0) -> dict:
    """Propagation matrix ``D^-1/2 (A + I) D^-1/2`` of a seeded undirected
    graph with exactly ``n_edges`` distinct non-loop edges.

    Endpoints are drawn with popularity ``(rank+1)^-alpha`` over a random
    node order, so degrees are skewed as in citation graphs.  Returns CSR
    (``indptr``, ``indices``, ``vals``) with the COO row of every entry
    (``rows``): ``2 * n_edges + n_nodes`` entries, symmetric, each row's
    columns sorted."""
    rng = np.random.default_rng(seed)
    w = np.arange(1, n_nodes + 1, dtype=np.float64) ** -alpha
    w = w[rng.permutation(n_nodes)]
    w /= w.sum()
    keys = np.empty(0, np.int64)
    while keys.size < n_edges:
        u, v = rng.choice(n_nodes, size=(2, 2 * n_edges), p=w)
        lo, hi = np.minimum(u, v), np.maximum(u, v)
        new = (lo * n_nodes + hi)[lo != hi]
        keys = np.concatenate([keys, new])
        _, first = np.unique(keys, return_index=True)
        keys = keys[np.sort(first)]  # distinct, in order of drawing
    keys = keys[:n_edges]
    lo, hi = keys // n_nodes, keys % n_nodes
    loops = np.arange(n_nodes, dtype=np.int64)
    rows = np.concatenate([lo, hi, loops])
    cols = np.concatenate([hi, lo, loops])
    order = np.lexsort((cols, rows))
    rows, cols = rows[order], cols[order]
    deg = np.bincount(rows, minlength=n_nodes).astype(np.float64)
    vals = (deg[rows] * deg[cols]) ** -0.5
    indptr = np.zeros(n_nodes + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n_nodes), out=indptr[1:])
    return {"indptr": indptr.astype(np.int32), "indices": cols.astype(np.int32),
            "vals": vals.astype(np.float32), "rows": rows.astype(np.int32),
            "shape": (n_nodes, n_nodes)}


def config_graph(cfg: dict) -> dict:
    """The graph of a configuration, from its counts and ``graph_seed``."""
    return gcn_graph(cfg["n_nodes"], cfg["n_edges"], alpha=cfg["degree_alpha"],
                     seed=cfg["graph_seed"])


def seed_key(seed: int):
    """A threefry key from a seed of up to 64 bits, as ``PRNGKey`` makes
    one from a 64-bit seed, without needing 64-bit mode."""
    import jax.numpy as jnp

    if seed < 0 or seed >> 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return jnp.asarray(np.array([(seed >> 32) & _WORD, seed & _WORD], np.uint32))


def propagate(rows, cols, vals, b, n_rows: int):
    """``A @ b`` for a COO matrix: gather, scale, segment sum (float32)."""
    import jax

    return jax.ops.segment_sum(vals[:, None] * b[cols], rows,
                               num_segments=n_rows)


def make_inputs(cfg: dict, graph: dict, seed: int) -> dict:
    """Features, teacher labels, training nodes, initial parameters and
    the dropout key of one run, all on the device but the training-node
    choice, which is a host pass over the labels.

    Features are dense standard normal.  Labels are the argmax of one
    propagation of the features through a random linear teacher, so they
    follow the graph.  ``train_per_class`` nodes of each class, drawn
    from the seed, are the labelled ones; the forward runs over all."""
    import jax
    import jax.numpy as jnp

    n, f, c, h = cfg["n_nodes"], cfg["n_features"], cfg["n_classes"], cfg["hidden"]
    k_x, k_t, k_p, k_drop, k_pick = jax.random.split(seed_key(seed), 5)

    @jax.jit
    def features_and_labels(k_x, k_t, rows, cols, vals):
        x = jax.random.normal(k_x, (n, f), jnp.float32)
        teacher = jax.random.normal(k_t, (f, c), jnp.float32)
        xt = jnp.matmul(x, teacher, precision=jax.lax.Precision.HIGHEST)
        return x, jnp.argmax(propagate(rows, cols, vals, xt, n), axis=-1)

    x, y = features_and_labels(k_x, k_t, jnp.asarray(graph["rows"]),
                               jnp.asarray(graph["indices"]),
                               jnp.asarray(graph["vals"]))
    labels = np.asarray(y)
    rng = np.random.default_rng(np.asarray(k_pick).tolist())
    per = cfg["train_per_class"]
    train = []
    for cls in range(c):
        members = np.flatnonzero(labels == cls)
        if members.size < per:
            raise ValueError(f"class {cls} has {members.size} nodes, fewer "
                             f"than the {per} labelled ones asked for")
        train.append(np.sort(rng.choice(members, size=per, replace=False)))
    return {"x": x, "y": y, "train": jnp.asarray(np.concatenate(train), jnp.int32),
            "params": init_params(k_p, f, h, c), "dropout_key": k_drop}


def init_params(key, f: int, h: int, c: int) -> dict:
    """Glorot-uniform weights and zero biases of the two layers, in one
    jitted call on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        k0, k1 = jax.random.split(key)

        def glorot(k, shape):
            lim = (6.0 / sum(shape)) ** 0.5
            return jax.random.uniform(k, shape, jnp.float32, -lim, lim)

        return {"w0": glorot(k0, (f, h)), "b0": jnp.zeros((h,), jnp.float32),
                "w1": glorot(k1, (h, c)), "b1": jnp.zeros((c,), jnp.float32)}

    return make(key)


def dropout(x, key, rate: float):
    """Inverted dropout of ``x`` at ``rate`` with the mask from ``key``."""
    import jax
    import jax.numpy as jnp

    if rate == 0.0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def step_key(key, step):
    """The dropout key of training step ``step`` (counted from 0)."""
    import jax

    return jax.random.fold_in(key, step)
