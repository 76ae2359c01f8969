"""Operations and bytes of OGB's L-layer GCN training step, from logical
shapes alone: its forward SpMMs and their least time, the step's model
operations, and the bytes a windowed eb launch's schedule moves.

As in ``bench.counts``, the logical counts never look at padded lanes,
tiles or a kernel's own streams: they read the same work whatever
implements it.  :func:`scheduled_bytes` alone reads the windowed
partition the run used, to say how much traffic its windows add.
"""
from __future__ import annotations

import numpy as np

from bench.counts import F32, I32, least_time_s, spmm_bytes, spmm_flops


def forward_spmms(cfg: dict) -> list[dict]:
    """The logical SpMM of each layer's forward, each with its bias:
    ``A (H W) + b`` of width ``hidden`` for the hidden layers, then
    ``n_classes``."""
    n, nnz = cfg["n_nodes"], cfg["n_entries"]
    out = [cfg["hidden"]] * (cfg["layers"] - 1) + [cfg["n_classes"]]
    return [{"n_rows": n, "n_cols": n, "nnz": nnz, "n": w, "bias": True}
            for w in out]


def forward_spmm_least_time_s(cfg: dict, peak: dict) -> float:
    """Sum over the forward SpMMs of max(operations over the bf16 peak,
    compulsory bytes (``bench.counts.spmm_bytes``) over the HBM peak)."""
    return sum(least_time_s(spmm_flops(s["nnz"], s["n"]),
                            spmm_bytes(s["n_rows"], s["n_cols"], s["nnz"],
                                       s["n"], bias=s["bias"]), peak)[0]
               for s in forward_spmms(cfg))


def train_flops(cfg: dict) -> int:
    """Model operations of one training step: each layer's ``H W`` and
    ``A (H W)``; backward, each layer's ``A^T dZ``, its weight gradient,
    and, above the first layer, its input gradient.  The norms,
    activations, dropout, loss and optimizer are elementwise and not
    counted, nor is recomputation."""
    n, nnz = cfg["n_nodes"], cfg["n_entries"]
    mm = lambda m, k, p: 2 * m * k * p  # noqa: E731
    dims = ([cfg["n_features"]] + [cfg["hidden"]] * (cfg["layers"] - 1)
            + [cfg["n_classes"]])
    total = 0
    for i, (d_in, d_out) in enumerate(zip(dims, dims[1:])):
        total += mm(n, d_in, d_out) + spmm_flops(nnz, d_out)  # forward
        total += spmm_flops(nnz, d_out) + mm(d_in, n, d_out)  # A^T dZ, dW
        if i:
            total += mm(n, d_out, d_in)  # dH
    return total


def scheduled_bytes(tile_rows, tile_cols, *, nnz_padded: int, n_rows: int,
                    n_cols: int, windows: tuple, n: int, col_tile: int,
                    bias: bool, window_reduce: bool) -> int:
    """HBM bytes a windowed eb launch's schedule moves, for each column
    tile of the padded width: the B block of the tile's column window
    each time consecutive tiles change it (its rows within the matrix),
    each output row once, every lane's row and column index and value
    (the row index twice where the window reduce reads it as a vector
    too), and the bias; and the tiles' window ids once."""
    tile_cols = np.asarray(tile_cols)
    col_window = windows[1]
    fetched = np.flatnonzero(np.diff(tile_cols, prepend=-1) != 0)
    b_rows = sum(min(col_window, n_cols - int(tile_cols[i]) * col_window)
                 for i in fetched)
    lane = (3 + window_reduce) * I32
    per_tile = ((b_rows + n_rows) * col_tile * F32 + nnz_padded * lane
                + (col_tile * F32 if bias else 0))
    return -(-n // col_tile) * per_tile + 2 * I32 * len(tile_rows)
