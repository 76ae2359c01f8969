"""Synthetic sparse matrix generators (uniform and power-law row lengths).

The paper evaluates on the DA-SpMM matrix suite (SuiteSparse-derived).
Offline we synthesize matrices with controlled statistics instead: density,
row-length skew (CV), and size — the three features the data-aware selector
conditions on.
"""
from __future__ import annotations

import numpy as np

from .formats import COO, CSR


def random_csr(
    n_rows: int,
    n_cols: int,
    density: float = 0.01,
    skew: float = 0.0,
    seed: int = 0,
    dtype=np.float32,
) -> CSR:
    """Random CSR with expected ``density`` and row-length skew.

    skew = 0.0 -> uniform Bernoulli rows; skew > 0 -> power-law row lengths
    (a few very long rows), the regime where nnz-split + segment reduction
    wins in the paper.
    """
    rng = np.random.default_rng(seed)
    target_nnz = max(1, int(n_rows * n_cols * density))
    if skew <= 0.0:
        lengths = rng.multinomial(target_nnz, np.full(n_rows, 1.0 / n_rows))
    else:
        w = rng.pareto(1.0 / max(skew, 1e-3), size=n_rows) + 1e-6
        w = w / w.sum()
        lengths = rng.multinomial(target_nnz, w)
    lengths = np.minimum(lengths, n_cols)

    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(lengths, out=indptr[1:])
    nnz = int(indptr[-1])
    indices = np.empty(nnz, np.int32)
    for r in range(n_rows):
        k = lengths[r]
        if k:
            indices[indptr[r]: indptr[r + 1]] = np.sort(
                rng.choice(n_cols, size=k, replace=False)
            )
    vals = rng.standard_normal(nnz).astype(dtype)
    import jax.numpy as jnp

    return CSR(
        indptr=jnp.asarray(indptr, jnp.int32),
        indices=jnp.asarray(indices),
        vals=jnp.asarray(vals),
        shape=(n_rows, n_cols),
    )


def random_coo(n_rows, n_cols, density=0.01, skew=0.0, seed=0) -> COO:
    """Random COO with the same parameters as :func:`random_csr`."""
    return random_csr(n_rows, n_cols, density, skew, seed).tocoo()


def _csr_from_lengths(lengths, n_cols: int, rng, dtype=np.float32) -> CSR:
    """CSR with the given per-row nnz counts and random sorted column
    picks — the shared materialization step of every generator here."""
    lengths = np.minimum(np.asarray(lengths, np.int64), n_cols)
    n_rows = lengths.shape[0]
    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(lengths, out=indptr[1:])
    nnz = int(indptr[-1])
    indices = np.empty(nnz, np.int32)
    for r in range(n_rows):
        k = lengths[r]
        if k:
            indices[indptr[r]: indptr[r + 1]] = np.sort(
                rng.choice(n_cols, size=k, replace=False))
    vals = rng.standard_normal(nnz).astype(dtype)
    import jax.numpy as jnp

    return CSR(indptr=jnp.asarray(indptr, jnp.int32),
               indices=jnp.asarray(indices), vals=jnp.asarray(vals),
               shape=(n_rows, n_cols))


def power_law_csr(n_rows: int, n_cols: int, *, avg_degree: float = 8.0,
                  alpha: float = 2.0, seed: int = 0) -> CSR:
    """Power-law (Zipf-degree) CSR — the web/social-graph regime the
    two-level skew schedule targets (DESIGN.md §11).

    Row ``r`` (after a random permutation) draws its expected degree from
    ``(r+1)^-alpha``, normalized so the mean degree is ``avg_degree``: a
    handful of hub rows hold a large share of the nnz while most rows
    keep one or two entries.  Smaller ``alpha`` flattens the curve.
    """
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, n_rows + 1, dtype=np.float64)
    w = ranks ** -alpha
    w *= (avg_degree * n_rows) / w.sum()
    lengths = rng.poisson(w)
    lengths[0] = max(lengths[0], 1)  # keep at least one hub non-empty
    rng.shuffle(lengths)
    return _csr_from_lengths(lengths, n_cols, rng)


#: Degree-profile presets mirroring common real-graph families:
#: (avg_degree, alpha).  'web'/'social' are heavy-hub power laws (web
#: link graphs are the more extreme), 'roadnet' is near-regular (planar
#: graphs have degree ~2-4 and no hubs) — the control case where skew
#: scheduling should *not* win.
GRAPH_PATTERNS = {
    "web": (10.0, 2.2),
    "social": (16.0, 1.6),
    "roadnet": (3.0, 0.05),
}


def graph_pattern_csr(pattern: str, n_rows: int, n_cols: int | None = None,
                      *, seed: int = 0) -> CSR:
    """CSR with the degree profile of a named real-graph family
    (:data:`GRAPH_PATTERNS`); square adjacency shape unless ``n_cols``
    is given."""
    try:
        avg_degree, alpha = GRAPH_PATTERNS[pattern]
    except KeyError:
        raise ValueError(f"unknown graph pattern {pattern!r}; "
                         f"known: {sorted(GRAPH_PATTERNS)}") from None
    return power_law_csr(n_rows, n_cols if n_cols is not None else n_rows,
                         avg_degree=avg_degree, alpha=alpha, seed=seed)


#: Node, undirected-edge, feature and class counts of Planetoid PubMed
#: (Kipf & Welling, ICLR 2017, Table 1) — the GCN workload's published
#: widths.  The graph itself is generated (:func:`gcn_graph_csr`).
PUBMED = {"n_nodes": 19717, "n_edges": 44338, "n_features": 500,
          "n_classes": 3}


def gcn_graph_csr(n_nodes: int, n_edges: int, *, alpha: float = 0.5,
                  seed: int = 0) -> CSR:
    """GCN propagation matrix ``D^-1/2 (A + I) D^-1/2`` of a seeded
    undirected graph with exactly ``n_edges`` distinct non-loop edges:
    ``2 * n_edges + n_nodes`` stored entries, symmetric, rows sorted.

    Endpoints are drawn with popularity ``(rank+1)^-alpha`` over a random
    node order, so degrees are skewed as in citation graphs (a few hubs,
    most nodes with one or two neighbours) rather than uniform."""
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
    import jax.numpy as jnp

    return CSR(indptr=jnp.asarray(indptr, jnp.int32),
               indices=jnp.asarray(cols, jnp.int32),
               vals=jnp.asarray(vals, jnp.float32),
               shape=(n_nodes, n_nodes))


#: Row-length quantile levels exposed in :func:`matrix_stats` (as
#: percent keys): the skew candidate generator reads q50/q90/q99 to
#: place split/merge thresholds, and the cost model interpolates the
#: curve to estimate how many rows each threshold captures.
_STAT_QUANTILES = (50, 90, 99)


def matrix_stats(csr: CSR) -> dict:
    """Features used by the data-aware schedule selector and the tuner.

    ``row_quantiles`` is a tuple of ``(percent, length)`` pairs over the
    *non-empty* row-length histogram — the same histogram the cache
    fingerprint hashes, so any schedule decision derived from it replays
    measurement-free on a fingerprint hit.
    """
    lengths = np.asarray(csr.row_lengths())
    mean = float(lengths.mean()) if lengths.size else 0.0
    std = float(lengths.std()) if lengths.size else 0.0
    nonzero = lengths[lengths > 0]
    if nonzero.size:
        quants = tuple(
            (p, int(round(float(np.quantile(nonzero, p / 100.0)))))
            for p in _STAT_QUANTILES)
    else:
        quants = tuple((p, 0) for p in _STAT_QUANTILES)
    return {
        "n_rows": csr.shape[0],
        "n_cols": csr.shape[1],
        "nnz": csr.nnz,
        "density": csr.nnz / max(1, csr.shape[0] * csr.shape[1]),
        "row_mean": mean,
        "row_cv": (std / mean) if mean > 0 else 0.0,
        "row_max": int(lengths.max()) if lengths.size else 0,
        "row_quantiles": quants,
    }
