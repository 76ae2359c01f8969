#!/usr/bin/env python3
"""Readings that the limits of a training cell are set from.

    python3 bench/readings.py --workload <cell> --seeds 12 --first-seed <n> \\
        --out readings-<cell>.json

In one process, at the cell's own size, for each seed: the program's
first steps through the compiled step the window drives, against the
float32 reference (the lower readings); the control, the reference with
every dense product at precision ``high`` in the program's place (and
the same written out, :data:`CONTROLS`); and
the planted faults of :data:`FAULTS`, also in the reference's place:
half of the labelled nodes left out of the loss (the mean taken over
the rest), the logits altered where they are produced, and the
optimizer's state not carried from step to step (its moments, or all of
it with the step count).  A step that returns its state
unchanged reads 1 on ``grad`` and ``update`` by their measure and needs
no run.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as harness  # noqa: E402

#: The control, XLA's own precision ``high``, and beside it the same three
#: bfloat16 passes written out (what the tests run on the CPU): dense
#: products of ``bench.reference``
CONTROLS = {"control": "dot_high", "control_written_out": "dot_bf16x3"}
#: Planted faults, each a variant of the reference put in the program's place
FAULTS = {
    "half_batch": {"labelled": slice(None, None, 2)},
    "altered_logits": {"tamper": lambda logits: logits.at[:, 0].add(0.05)},
    "fresh_moments": {"reset": "moments"},
    "fresh_state": {"reset": "all"},
}


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

    from bench import check, reference
    from bench.modes import train
    from bench.traffic import gcn as traffic

    spec = harness.cell_spec(args.workload)
    cfg = spec["config"]
    harness.find_chips(spec["cell"]["chips"])
    graph = traffic.config_graph(cfg)
    rows = []
    t0 = time.perf_counter()
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        program = train.build_program(cfg, graph)
        first = traffic.make_inputs(cfg, graph, args.first_seed)
        program = train.compile_step(program, first)
    steps = {"reference": {}, **FAULTS,
             **{k: {"dot": getattr(reference, v)} for k, v in CONTROLS.items()}}
    steps = {k: reference.make_train_step(cfg, graph, **v) for k, v in steps.items()}
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        inputs = traffic.make_inputs(cfg, graph, seed)
        snap, _ = train.first_steps(program, inputs)
        ref = reference.first_steps(cfg, graph, inputs, step=steps["reference"])
        row = {"seed": seed, "program": check.numbers(snap, ref)}
        for name in (*CONTROLS, *FAULTS):
            row[name] = check.numbers(reference.first_steps(
                cfg, graph, inputs, step=steps[name]), ref)
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
