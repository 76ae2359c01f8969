#!/usr/bin/env python3
"""Smoke run of the GCN SpMM path, compiled on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the distributed SpMM on four chips

The graph is generated from ``--seed`` with the node, edge, feature and
class counts of Planetoid PubMed (19,717 nodes, 44,338 undirected edges
plus self-loops, symmetric-normalised; 500 features, 3 classes; hidden
width 16, as in Kipf & Welling's GCN).  Phases, all in this one process:

  device     JAX must find a TPU and the Pallas kernels must run compiled;
  parity     ``repro.sparse.spmm`` under ``"auto"``, the three eb
             strategies and one rb schedule against ``impl="ref"``;
  training   jitted value-and-grad steps of ``gcn_two_layer``: finite,
             falling loss and finite grads;
  kernels    the compiled step's HLO holds the SpMM launches as
             ``tpu_custom_call``.

With ``--chips 4`` it runs only ``dist_spmm`` over a four-chip mesh under
each collective, against single-device ``spmm`` on the same graph (3
isolated nodes pad it to 19,720 so rows split evenly).

Diagnostics go to earlier lines; the last line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A failed phase, or no TPU, exits non-zero and prints no such line.  The
times printed are smoke readings of one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

HIDDEN = 16  # Kipf & Welling's hidden width
TRAIN_PER_CLASS = 20  # Planetoid split: 20 labelled nodes per class
LR = 0.5
WEIGHT_DECAY = 5e-4
RTOL = ATOL = 1e-4  # f32 parity, as the repo's kernel tests use


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_check(chips: int):
    import jax

    from repro.kernels.common import pallas_interpret_default

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX finds no TPU (platform {devs[0].platform!r})")
    check(not pallas_interpret_default(),
          "Pallas kernels would run interpreted")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    return devs


def parity_schedules():
    """The schedules the parity phase runs: ``"auto"``, each built-in eb
    strategy, and rb.  'parallel' reduces a whole group into one row, so
    it runs on the split layout that makes every group single-row."""
    from repro.sparse import Schedule

    return {
        "auto": "auto",
        "eb/segment": Schedule("eb", strategy="segment"),
        "eb/parallel": Schedule("eb", strategy="parallel", group_size=8,
                                split_threshold=1),
        "eb/accumulate": Schedule("eb", strategy="accumulate"),
        "rb": Schedule("rb"),
    }


def max_err(got, want) -> float:
    import numpy as np

    return float(np.max(np.abs(np.asarray(got) - np.asarray(want))))


def forward_parity(adj, seed: int) -> None:
    import jax
    import numpy as np

    from repro.sparse import Schedule, matrix_stats, spmm

    xw = jax.random.normal(jax.random.PRNGKey(seed), (adj.shape[1], HIDDEN))
    want = np.asarray(spmm(adj, xw, impl="ref"))
    log(f"parity: auto picks {Schedule.auto(matrix_stats(adj), HIDDEN)}")
    for name, sched in parity_schedules().items():
        t0 = time.perf_counter()
        got = jax.block_until_ready(spmm(adj, xw, schedule=sched))
        secs = time.perf_counter() - t0
        err = max_err(got, want)
        log(f"parity {name}: max|err| {err:.3e}, first call {secs:.2f} s "
            f"(compile included; smoke reading)")
        check(got.shape == want.shape, f"{name}: shape {got.shape}")
        check(bool(np.allclose(got, want, rtol=RTOL, atol=ATOL)),
              f"{name}: max|err| {err:.3e} over rtol=atol={RTOL}")


def make_task(adj, seed: int, n_features: int, n_classes: int):
    """Features, teacher labels and the Planetoid-style training mask."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.sparse import spmm

    k_x, k_t = jax.random.split(jax.random.PRNGKey(seed))
    n = adj.shape[0]
    x = jax.random.normal(k_x, (n, n_features), jnp.float32)
    teacher = jax.random.normal(k_t, (n_features, n_classes), jnp.float32)
    y = np.asarray(jnp.argmax(spmm(adj, x @ teacher, impl="ref"), -1))
    train = np.concatenate([np.flatnonzero(y == c)[:TRAIN_PER_CLASS]
                            for c in range(n_classes)])
    return x, jnp.asarray(y), jnp.asarray(train)


def init_params(seed: int, n_features: int, n_classes: int):
    import jax
    import jax.numpy as jnp

    def glorot(key, shape):
        lim = (6.0 / sum(shape)) ** 0.5
        return jax.random.uniform(key, shape, jnp.float32, -lim, lim)

    k0, k1 = jax.random.split(jax.random.PRNGKey(seed + 1))
    return {"w0": glorot(k0, (n_features, HIDDEN)),
            "b0": jnp.zeros((HIDDEN,), jnp.float32),
            "w1": glorot(k1, (HIDDEN, n_classes)),
            "b1": jnp.zeros((n_classes,), jnp.float32)}


def gcn_loss(adj, schedule):
    """The training objective: masked cross-entropy of the two-layer GCN
    (``gcn_two_layer``, the fusion planner's two SpMM launches) plus L2
    on the first layer's weights."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import gcn_two_layer

    def loss_fn(p, x, y, train):
        logits = gcn_two_layer(adj, x, p["w0"], p["w1"], p["b0"], p["b1"],
                               schedule=schedule)
        logp = jax.nn.log_softmax(logits[train])
        nll = -jnp.mean(jnp.take_along_axis(logp, y[train][:, None], 1))
        return nll + WEIGHT_DECAY * jnp.sum(p["w0"] ** 2)

    return loss_fn


def train_steps(adj, seed: int, n_features: int, n_classes: int,
                steps: int) -> str:
    """Jitted value-and-grad GCN steps with a plain gradient update;
    returns the compiled step's HLO text.

    The schedule is chosen (``Schedule.auto``) and the adjacency's feed
    formats are built by one eager forward before tracing: both are
    host-side passes over concrete indices, memoized on the matrix."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.sparse import Schedule, matrix_stats

    x, y, train = make_task(adj, seed, n_features, n_classes)
    params = init_params(seed, n_features, n_classes)
    sched = Schedule.auto(matrix_stats(adj), HIDDEN)
    loss_fn = gcn_loss(adj, sched)
    t0 = time.perf_counter()
    first = float(loss_fn(params, x, y, train))
    log(f"training: schedule {sched}; eager forward loss {first:.5f} in "
        f"{time.perf_counter() - t0:.2f} s (format build included)")
    t0 = time.perf_counter()
    step = jax.jit(jax.value_and_grad(loss_fn)).lower(
        params, x, y, train).compile()
    log(f"training: step compile {time.perf_counter() - t0:.2f} s "
        f"(smoke reading)")
    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss, grads = jax.block_until_ready(step(params, x, y, train))
        times.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(loss))
        check(all(bool(jnp.all(jnp.isfinite(g)))
                  for g in jax.tree.leaves(grads)), "non-finite gradient")
        params = jax.tree.map(lambda p, g: p - LR * g, params, grads)
    log("training: loss " + " ".join(f"{v:.5f}" for v in losses))
    log("training: step ms " + " ".join(f"{t:.2f}" for t in times)
        + " (smoke readings, not benchmark numbers)")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return step.as_text()


def compiled_kernels(hlo: str) -> None:
    launches = [ln for ln in hlo.splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln]
    log(f"kernels: {len(launches)} tpu_custom_call launches in the step")
    names = sorted({ln.split("=", 1)[0].strip().split()[-1]
                    for ln in launches})
    log("kernels: " + ", ".join(names))
    # the two planned SpMM launches (DESIGN.md §10) run as Mosaic kernels
    check(sum("spmm_eb" in n or "spmm_rb" in n for n in names) >= 2,
          "the compiled step holds fewer than two SpMM kernel launches")


def placed_operands(adj, mesh, mode: str, nnz_tile: int):
    """``dist_spmm``'s partitioned COO for ``mode``, placed as its
    shard_map in_specs split it (``P("shards")``); checks that each
    shard of rows, cols and vals sits on its own chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sparse.distributed import partition_nnz_coo, partition_rows_coo

    part = partition_rows_coo if mode == "row" else partition_nnz_coo
    chips = mesh.shape["shards"]
    rows, cols, vals, _ = part(adj, chips, nnz_tile)
    placed = [jax.device_put(x, NamedSharding(mesh, P("shards")))
              for x in (rows, cols, vals)]
    want = sorted(d.id for d in mesh.devices.flat)
    for name, x in zip(("rows", "cols", "vals"), placed):
        shards = x.addressable_shards
        on = sorted(s.device.id for s in shards)
        starts = {s.index[0].start or 0 for s in shards}
        check(on == want and len(starts) == chips,
              f"{mode}: {name} shards on devices {on}, blocks at {starts}")
    log(f"dist {mode}: rows/cols/vals split {rows.shape[0] // chips} "
        f"lanes per chip, one shard on each of devices {want}")
    return placed


def dist_phase(seed: int, chips: int) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_reduction_mesh
    from repro.sparse import Schedule, dist_spmm, spmm
    from repro.sparse.distributed import spmm_shard_map
    from repro.sparse.random import PUBMED, gcn_graph_csr

    n = -(-PUBMED["n_nodes"] // chips) * chips
    adj = gcn_graph_csr(n, PUBMED["n_edges"], seed=seed)
    mesh = make_reduction_mesh(chips)
    devs = list(mesh.devices.flat)
    check(len({d.id for d in devs}) == chips,
          f"mesh is not {chips} distinct chips: {devs}")
    b = jax.random.normal(jax.random.PRNGKey(seed), (n, HIDDEN))
    want = np.asarray(spmm(adj, b, impl="ref"))
    one = jax.block_until_ready(spmm(adj, b, schedule=Schedule("eb")))
    log(f"dist: {n} nodes, single-device eb max|err| vs ref "
        f"{max_err(one, want):.3e}")
    check(bool(np.allclose(one, want, rtol=RTOL, atol=ATOL)),
          "single-device spmm disagrees with ref")
    for mode in ("row", "nnz_ar", "nnz_rs"):
        sched = Schedule("eb", collective=mode)
        t0 = time.perf_counter()
        out = jax.block_until_ready(dist_spmm(
            adj, b, mesh=mesh, axis="shards", schedule=sched))
        secs = time.perf_counter() - t0
        shards = out.addressable_shards
        on = sorted(s.device.id for s in shards)
        blocks = sorted({s.index[0].start or 0 for s in shards})
        err = max_err(out, one)
        log(f"dist {mode}: max|err| vs single device {err:.3e}, shards on "
            f"devices {on}, row blocks at {blocks}, first call {secs:.2f} s "
            f"(compile included; smoke reading)")
        check(on == sorted(d.id for d in devs),
              f"{mode}: shards not one per chip: {on}")
        if mode != "nnz_ar":  # row-sharded result: one row block per chip
            check(len(blocks) == chips, f"{mode}: row blocks {blocks}")
        check(bool(np.allclose(out, one, rtol=RTOL, atol=ATOL)),
              f"{mode}: max|err| {err:.3e} vs single device")
        # the same launch over operands already split one shard per chip
        placed = placed_operands(adj, mesh, mode, sched.nnz_tile)
        got = jax.block_until_ready(spmm_shard_map(
            *placed, b, n_rows=n, mesh=mesh, axis="shards", schedule=sched))
        check(bool(np.allclose(got, out, rtol=RTOL, atol=ATOL)),
              f"{mode}: placed operands give max|err| {max_err(got, out)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.launch.backend import setup
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    info = setup()
    log(f"backend: {info}")

    phase = "device"
    try:
        devs = device_check(args.chips)
        if args.chips > 1:
            phase = "dist"
            dist_phase(args.seed, args.chips)
        else:
            from repro.sparse.random import PUBMED, gcn_graph_csr

            phase = "graph"
            t0 = time.perf_counter()
            adj = gcn_graph_csr(PUBMED["n_nodes"], PUBMED["n_edges"],
                                seed=args.seed)
            log(f"graph: {adj.shape[0]} nodes, {adj.nnz} entries, "
                f"built in {time.perf_counter() - t0:.2f} s")
            phase = "parity"
            forward_parity(adj, args.seed)
            phase = "training"
            hlo = train_steps(adj, args.seed, PUBMED["n_features"],
                              PUBMED["n_classes"], args.steps)
            phase = "kernels"
            compiled_kernels(hlo)
    except Exception:  # the boundary: report the phase and fail the run
        traceback.print_exc()
        print(f"chip_smoke: phase {phase!r} failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
