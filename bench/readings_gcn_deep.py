#!/usr/bin/env python3
"""Readings that the limits of the deep GCN training cell are set from.

    python3 bench/readings_gcn_deep.py --workload gcn-arxiv.train \\
        --seeds 8 --first-seed <n> --out readings-gcn-arxiv.train.json

In one process, at the cell's own size, for each seed: the program's
first steps through the compiled step the window drives, against the
float32 reference following its parameters (the lower readings), and
against the reference left to go its own way (``program_apart``, which
the cell does not compare); the control, the reference at matmul
precision ``default`` (and the same bfloat16 pass written out) in the
program's place; and the planted faults of
``bench.reference_gcn_deep.FAULTS`` (half of the training nodes left out
of the loss, the logits altered), also in the reference's place.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as harness  # noqa: E402


def rows(cfg: dict, seeds: list):
    """Each seed's numbers of the deep GCN training cell, by kind."""
    import jax

    from bench import check, reference_gat, reference_gcn_deep
    from bench.modes import train_gcn_deep as mode
    from bench.traffic import gcn
    from bench.traffic import gcn_deep as traffic

    graph = gcn.config_graph(cfg)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        first = traffic.make_inputs(cfg, graph, seeds[0])
        program = mode.compile_step(mode.build_program(cfg, graph, first), first)
    variants = {"reference": {}, "control": {"precision": "default"},
                "control_written_out": {"dot": reference_gat.dot_bf16},
                **reference_gcn_deep.FAULTS}
    steps = {k: reference_gcn_deep.make_train_step(cfg, graph, **v)
             for k, v in variants.items()}

    def against_reference(snap, inputs):
        return check.numbers(snap, reference_gcn_deep.first_steps(
            cfg, graph, inputs, step=steps["reference"], follow=snap))

    for seed in seeds:
        inputs = traffic.make_inputs(cfg, graph, seed)
        snap, _ = mode.first_steps(program, inputs)
        apart = reference_gcn_deep.first_steps(cfg, graph, inputs,
                                               step=steps["reference"])
        row = {"seed": seed, "program": against_reference(snap, inputs),
               "program_apart": check.numbers(snap, apart)}
        for name in variants:
            if name != "reference":
                bad = reference_gcn_deep.first_steps(cfg, graph, inputs,
                                                     step=steps[name])
                row[name] = against_reference(bad, inputs)
        yield row


def main(argv=None) -> int:
    """Print and write the readings; see the module's docstring."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    harness.prepare()
    import jax

    spec = harness.cell_spec(args.workload)
    harness.find_chips(spec["cell"]["chips"])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    t0 = time.perf_counter()
    found = []
    for row in rows(spec["config"], seeds):
        found.append(row)
        print(json.dumps(row), flush=True)
    kinds = [k for k in found[0] if k != "seed"]
    summary = {k: {n: {"max": max(r[k][n] for r in found),
                       "min": min(r[k][n] for r in found)}
                   for n in found[0]["program"]} for k in kinds}
    out = {"cell": args.workload, "device": jax.devices()[0].device_kind,
           "seconds": time.perf_counter() - t0, "rows": found,
           "summary": summary}
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
