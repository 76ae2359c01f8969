#!/usr/bin/env python3
"""Time one cell's eb SpMM launch alone on the chip it is started on.

    python3 bench/launch_time.py --workload <cell> [--layout graph|sparse]
        [--no-walk] [--launches 200] [--repeats 5] [--label <name>]

The launch is the program's ``kernels.ops.spmm`` over the cell's graph
under the schedule its selector picks, jitted with the graph closed over
as the training step holds it, at each width the step runs it at (the
configuration's ``hidden`` and ``n_classes``).  ``--layout sparse`` keeps
the graph's row count and schedule but gives every other row one entry
and the rest none, so each nnz tile spans about twice as many rows as
it has lanes: under 'segment' every tile then walks its runs.
``--no-walk`` takes the run walk out of the kernel (the registry's
in-kernel realizations become no-ops), which leaves the gather, the
scaling and, where the program has it, the window product: the split of
a launch into its parts.  Its sums are then wrong; only its time counts.

A launch's time is the median over ``--repeats`` of the host's time for
``--launches`` launches in a row, the device busy throughout.  One JSON
line per width on standard output.  Run it against another tree's
program with ``PYTHONPATH=<tree>/src``; it uses no more of the program
than its public ``spmm``, ``CSR`` and ``Schedule``.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent))

from bench.traffic import gcn as traffic  # noqa: E402


def sparse_graph(graph: dict, seed: int = 0) -> dict:
    """The graph's row and column counts, one entry (value 1) on every
    other row, none on the rest."""
    n_rows, n_cols = graph["shape"]
    rows = np.arange(0, n_rows, 2)
    cols = np.random.default_rng(seed).integers(0, n_cols, rows.size)
    indptr = np.zeros(n_rows + 1, np.int32)
    indptr[rows + 1] = 1
    return {"indptr": np.cumsum(indptr).astype(np.int32),
            "indices": cols.astype(np.int32),
            "vals": np.ones(rows.size, np.float32), "shape": (n_rows, n_cols)}


def take_out_walk() -> None:
    """Make the kernel's run walk a no-op (before any launch is traced)."""
    import importlib

    from repro.kernels import common

    def skip(*args, **kwargs):
        del args, kwargs

    common.group_reduce_scatter = skip
    importlib.import_module("repro.kernels.spmm_eb").group_reduce_scatter = skip


def feed_format(adj, sched):
    """The launch's lanes, built outside any trace: the program memoizes
    them on the matrix, as the benchmark's eager forward has them built."""
    return adj.grouped(sched.nnz_tile, group_size=sched.group_size,
                       split_threshold=sched.split_threshold,
                       merge_threshold=sched.merge_threshold)


def window_share(lanes, sched):
    """Percent of the launch's tiles that take the window, or None where
    the program has no window."""
    try:
        from repro.kernels.ops import eb_window_tiles
    except ImportError:
        return None
    return 100.0 * float(eb_window_tiles(lanes, sched.strategy).mean())


def time_launch(fn, b, launches: int, repeats: int) -> list:
    """Milliseconds a launch, ``repeats`` times, after a warm-up."""
    import jax

    jax.block_until_ready(fn(b))
    runs = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(launches):
            out = fn(b)
        jax.block_until_ready(out)
        runs.append((time.perf_counter() - t0) / launches * 1e3)
    return runs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layout", choices=("graph", "sparse"), default="graph")
    ap.add_argument("--no-walk", action="store_true")
    ap.add_argument("--launches", type=int, default=200)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--label", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import spmm
    from repro.sparse import CSR, Schedule, matrix_stats

    bm = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in bm["workloads"]}[args.workload]
    cfg_file = {c["name"]: c["file"] for c in bm["configs"]}[cell["config"]]
    cfg = json.loads((BENCH.parent / cfg_file).read_text())

    def csr(g):
        return CSR(indptr=jnp.asarray(g["indptr"]),
                   indices=jnp.asarray(g["indices"]),
                   vals=jnp.asarray(g["vals"]), shape=g["shape"])

    graph = traffic.config_graph(cfg)
    sched = Schedule.auto(matrix_stats(csr(graph)), cfg["hidden"])
    adj = csr(graph if args.layout == "graph" else sparse_graph(graph))
    if args.no_walk:
        take_out_walk()
    share = window_share(feed_format(adj, sched), sched)
    key = jax.random.PRNGKey(0)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        for width in (cfg["hidden"], cfg["n_classes"]):
            b = jax.random.normal(key, (adj.shape[1], width), jnp.float32)
            fn = jax.jit(lambda x: spmm(adj, x, sched))
            runs = time_launch(fn, b, args.launches, args.repeats)
            print(json.dumps({
                "label": args.label, "workload": args.workload,
                "layout": args.layout, "no_walk": args.no_walk, "width": width,
                "schedule": str(sched), "nnz": int(adj.nnz),
                "window_share": share, "median_ms": statistics.median(runs),
                "runs_ms": runs, "device": jax.devices()[0].device_kind}),
                flush=True)


if __name__ == "__main__":
    main()
