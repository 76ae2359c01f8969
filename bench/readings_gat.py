#!/usr/bin/env python3
"""Readings that the limits of the GAT training cell and of the GCN
inference cell are set from.

    python3 bench/readings_gat.py --workload <cell> --seeds 12 \\
        --first-seed <n> --out readings-<cell>.json

In one process, at the cell's own size, for each seed: the program's
result through the compiled step the window drives, against the float32
reference following its parameters (the lower readings), and against
the reference left to go its own way (``program_apart``, which the cell
does not compare); the control, the reference at matmul
precision ``default`` (and the same bfloat16 pass written out) in the
program's place; and the planted faults, also in the reference's place.
For ``gat-pubmed.train``: the coefficient dropout mask dropped, the score
without its LeakyReLU, the output heads concatenated
(``bench.reference_gat.FAULTS``).  For ``gcn-pubmed.infer``: both biases
dropped.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as harness  # noqa: E402


def gat_rows(cfg: dict, seeds: list):
    """Each seed's numbers of the GAT training cell, by kind."""
    import jax

    from bench import check, reference_gat
    from bench.modes import train as loop
    from bench.modes import train_gat
    from bench.traffic import gat as traffic
    from bench.traffic import gcn

    graph = gcn.config_graph(cfg)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        program = train_gat.build_program(cfg, graph)
        program = loop.compile_step(program, traffic.make_inputs(cfg, graph, seeds[0]))
    variants = {"reference": {}, "control": {"precision": "default"},
                "control_written_out": {"dot": reference_gat.dot_bf16},
                **reference_gat.FAULTS}
    steps = {k: reference_gat.make_train_step(cfg, graph, **v)
             for k, v in variants.items()}
    for seed in seeds:
        inputs = traffic.make_inputs(cfg, graph, seed)
        snap, _ = train_gat.first_steps(program, inputs)
        ref = reference_gat.first_steps(cfg, graph, inputs, step=steps["reference"])
        row = {"seed": seed, "program": check.numbers(snap, reference_gat.first_steps(
            cfg, graph, inputs, step=steps["reference"], follow=snap)),
            "program_apart": check.numbers(snap, ref)}
        for name in variants:
            if name != "reference":
                bad = reference_gat.first_steps(cfg, graph, inputs, step=steps[name])
                row[name] = check.numbers(bad, reference_gat.first_steps(
                    cfg, graph, inputs, step=steps["reference"], follow=bad))
        yield row


def infer_rows(cfg: dict, seeds: list):
    """Each seed's logit gap of the GCN inference cell, by kind."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import reference_gat
    from bench.modes import infer
    from bench.traffic import gcn

    graph = gcn.config_graph(cfg)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        program = infer.build_program(cfg, graph)
        first = infer.make_inputs(cfg, graph, seeds[0])
        compiled = program["step"].lower(first["params"], first["x"]).compile()
    default = lambda a, b: jnp.matmul(a, b, precision=jax.lax.Precision.DEFAULT)  # noqa: E731
    variants = {"reference": {}, "control": {"dot": default},
                "control_written_out": {"dot": reference_gat.dot_bf16},
                "dropped_bias": {"bias": False}}
    fwd = {k: jax.jit(lambda p, x, v=v: infer.forward(cfg, graph, p, x, **v))
           for k, v in variants.items()}
    for seed in seeds:
        inputs = infer.make_inputs(cfg, graph, seed)
        want = fwd["reference"](inputs["params"], inputs["x"])
        got = np.asarray(compiled(inputs["params"], inputs["x"]))
        row = {"seed": seed, "program": {"logits": infer.logit_gap(got, want)}}
        for name in variants:
            if name != "reference":
                row[name] = {"logits": infer.logit_gap(
                    fwd[name](inputs["params"], inputs["x"]), want)}
        yield row


def main(argv=None) -> int:
    """Print and write the readings; see the module's docstring."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    harness.prepare()
    import jax

    spec = harness.cell_spec(args.workload)
    harness.find_chips(spec["cell"]["chips"])
    make = {"train_gat": gat_rows, "infer": infer_rows}[spec["traffic"]["mode"]]
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    t0 = time.perf_counter()
    rows = []
    for row in make(spec["config"], seeds):
        rows.append(row)
        print(json.dumps(row), flush=True)
    kinds = [k for k in rows[0] if k != "seed"]
    summary = {k: {n: {"max": max(r[k][n] for r in rows),
                       "min": min(r[k][n] for r in rows)}
                   for n in rows[0]["program"]} for k in kinds}
    out = {"cell": args.workload, "device": jax.devices()[0].device_kind,
           "seconds": time.perf_counter() - t0, "rows": rows, "summary": summary}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
