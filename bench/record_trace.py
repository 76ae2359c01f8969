#!/usr/bin/env python3
"""Record a few traced steps of a training cell on the chip, and split
them by program scope and host phase.

    python3 bench/record_trace.py --workload <cell> --seed <n> --steps <k> [--out <dir>]

Sets the cell up as ``bench/run.py`` does, steps ``k`` times untraced,
then ``k`` times under the profiler inside the span ``bench.window``,
and prints one JSON line: the step time of both windows, the trace's
kernel and XLA time a step, the XLA time a step by scope
(``bench.attribution.xla_by_scope``), and the host's dispatch,
completion and between-step phases a step with the clock offset and its
bracket (``bench.attribution.host_phases``).  With ``--out`` it also
writes the trace (``<cell>.<k>-steps.xplane.pb``) and the compiled
step's HLO lines of the operations that ran, with their ``op_name``
and without kernel bodies (``<cell>.<k>-steps.hlo.txt``): the recorded
fixtures of ``bench/fixtures/`` come from here.
"""
from __future__ import annotations

import argparse
import json
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run as bench_run  # noqa: E402


def steps(program: dict, live: tuple, inputs: dict, k: int):
    """``k`` steps back to back, each ending in ``block_until_ready``;
    the window's length and the live state."""
    import jax

    from bench.modes import train

    step, args = program["compiled"], train.feed(inputs)
    params, state = live
    t0 = time.perf_counter()
    for _ in range(k):
        params, state, loss = step(params, state, *args)
        jax.block_until_ready(loss)
    return time.perf_counter() - t0, (params, state)


def hlo_lines(hlo_text: str, names) -> str:
    """The HLO lines of the instructions ``names``, backend configs (the
    kernel bodies among them) cut."""
    from bench import trace

    keep = [re.sub(r", backend_config=.*$", "", ln.rstrip())
            for ln in hlo_text.splitlines() if " = " in ln and trace.op_name(
                re.sub(r"^\s*ROOT\s+", "", ln)) in names]
    return "\n".join(keep) + "\n"


def main(argv=None) -> int:
    """Entry point; see the module's docstring."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    bench_run.prepare()
    import jax

    from bench import attribution, trace
    from bench.modes import train
    from bench.traffic import gcn as traffic

    spec = bench_run.cell_spec(args.workload)
    bench_run.find_chips(spec["cell"]["chips"])
    cfg = spec["config"]
    graph = traffic.config_graph(cfg)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        program = train.build_program(cfg, graph)
        inputs = traffic.make_inputs(cfg, graph, args.seed)
        program = train.compile_step(program, inputs)
    _, live = train.first_steps(program, inputs)
    untraced_s, live = steps(program, live, inputs, args.steps)
    logdir = tempfile.mkdtemp(prefix="bench-record-")
    try:
        jax.profiler.start_trace(logdir)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            traced_s, live = steps(program, live, inputs, args.steps)
        jax.profiler.stop_trace()
        xplane = trace.find_xplane(logdir)
        from jax.profiler import ProfileData

        profile = ProfileData.from_file(str(xplane))
        red, host = trace.reduce(profile), attribution.host_phases(profile)
        if args.out:
            args.out.mkdir(parents=True, exist_ok=True)
            stem = f"{args.workload}.{args.steps}-steps"
            shutil.copy(xplane, args.out / f"{stem}.xplane.pb")
            (args.out / f"{stem}.hlo.txt").write_text(
                hlo_lines(program["hlo"], set(red["ops"])))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)

    launches = trace.pallas_launches(program["hlo"])
    ms = lambda s: None if s is None else s * 1e3  # noqa: E731
    per_step = lambda s: None if s is None else s / args.steps * 1e3  # noqa: E731
    by_scope = attribution.xla_by_scope(red, program["hlo"], launches)
    phases = host["gap_phases_s"] or {}
    print(json.dumps({
        "cell": args.workload, "steps": args.steps,
        "step_ms_untraced": per_step(untraced_s), "step_ms_traced": per_step(traced_s),
        "launches": launches,
        "spmm_kernel_ms": per_step(trace.spmm_seconds(red, launches)),
        "xla_ms": per_step(trace.xla_seconds(red, launches)),
        "xla_by_scope_ms": {k: per_step(v) for k, v in sorted(by_scope.items())},
        "idle_ms": {k: per_step(v) for k, v in red["gaps"].items()},
        "dispatch_ms": ms(host["dispatch_s"]), "completion_ms": ms(host["completion_s"]),
        "gap_phases_ms": {k: per_step(v) for k, v in phases.items()} or None,
        "between_steps_ms": per_step(host["between_steps_s"]),
        "clock_offset_ms": ms(host["clock_offset_s"]),
        "clock_bracket_ms": ms(host["clock_bracket_s"]),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
