"""Operations and compulsory bytes of the work a step needs, from
logical shapes alone, and the peaks they are held against.

The counts never look at padded lanes, tiles or a schedule's own
streams, so they read the same work whatever implements it: a kernel
change moves the measured time, never the count.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"

F32 = 4  # bytes of a float32 value
I32 = 4  # bytes of an int32 index


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def spmm_flops(nnz: int, n: int) -> int:
    """A multiply and an add per stored entry and dense column."""
    return 2 * nnz * n


def spmm_bytes(n_rows: int, n_cols: int, nnz: int, n: int, *,
               bias: bool = False, itemsize: int = F32) -> int:
    """Compulsory traffic of ``out = A @ B (+ bias)`` with CSR ``A``: each
    entry's column index and value, the row pointers, the dense operand
    ``n_cols x n`` once, the output ``n_rows x n`` once, and the bias."""
    return (nnz * (I32 + itemsize) + (n_rows + 1) * I32
            + n_cols * n * itemsize + n_rows * n * itemsize
            + (n * itemsize if bias else 0))


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def gcn_spmm_launches(cfg: dict, backward=(False, False)) -> list[dict] | None:
    """The logical SpMMs of a two-layer GCN step, one for each SpMM kernel
    launch, given whether each launch, in the order the step runs them,
    is a backward one.  Forward: ``A @ (X W0) + b0`` (width ``hidden``),
    then ``A @ (H W1) + b1`` (width ``n_classes``), each with its bias
    epilogue; backward: ``A^T dZ1`` (width ``n_classes``), then
    ``A^T dZ0`` (width ``hidden``).  None where the launches of a kind
    are more than the step has SpMMs of it."""
    n, nnz = cfg["n_nodes"], cfg["n_entries"]
    widths = {False: [cfg["hidden"], cfg["n_classes"]],
              True: [cfg["n_classes"], cfg["hidden"]]}
    out = []
    for bwd in backward:
        if not widths[bwd]:
            return None
        out.append({"n_rows": n, "n_cols": n, "nnz": nnz,
                    "n": widths[bwd].pop(0), "bias": not bwd})
    return out


def spmm_least_time_s(launches: list[dict], peak: dict) -> float:
    """Sum over launches of each launch's least time."""
    total = 0.0
    for lc in launches:
        t, _ = least_time_s(spmm_flops(lc["nnz"], lc["n"]),
                            spmm_bytes(lc["n_rows"], lc["n_cols"], lc["nnz"],
                                       lc["n"], bias=lc["bias"]), peak)
        total += t
    return total


def gcn_train_flops(cfg: dict) -> int:
    """Model operations of one full-batch training step of a two-layer GCN:
    the dense matmuls and SpMMs of the forward, and of the backward as far
    as the trained parameters need it (no gradient for the features or the
    graph).  Elementwise work (activation, dropout, loss, optimizer) and
    recomputation are not counted."""
    n, nnz = cfg["n_nodes"], cfg["n_entries"]
    f, h, c = cfg["n_features"], cfg["hidden"], cfg["n_classes"]
    mm = lambda m, k, p: 2 * m * k * p  # noqa: E731
    forward = mm(n, f, h) + spmm_flops(nnz, h) + mm(n, h, c) + spmm_flops(nnz, c)
    backward = (spmm_flops(nnz, c)   # A^T dY
                + mm(h, n, c)        # dW1 = H^T dZ1
                + mm(n, c, h)        # dH = dZ1 W1^T
                + spmm_flops(nnz, h)  # A^T dH'
                + mm(f, n, h))       # dW0 = X^T dZ0
    return forward + backward
