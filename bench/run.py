#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the root of the
checkout: the cell's entry names its configuration (whose ``file`` holds
the sizes) and its traffic mix (``bench/traffic/<traffic>.json``, whose
``mode`` names the module in ``bench/modes/`` that runs it); the cell's
own file, ``bench/workloads/<cell>.json``, holds the limits of its
correctness check and the readings they were set from; each per-layer
metric is read by ``bench/metrics/<metric>.py``.  No list of cells or metrics is kept
in code.

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer ones.  The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared beside its limit, also printed as the last lines of
standard error).  Without a TPU, on a chip whose ``device_kind`` has no
peaks, with fewer chips than the cell asks for, or without the program
beside it, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
#: JAX's persistent compilation cache: a fixed directory in the checkout
COMPILE_CACHE = ROOT / ".jax_cache"
#: host memory the TPU runtime maps at its start for transfers; mapping
#: its default without transparent hugepages adds 5 s to a run's set-up,
#: varying by seconds, and no cell moves more than a few MB to or from
#: the chip
PREMAPPED_BYTES = 256 << 20


class NoChip(RuntimeError):
    """The machine lacks the chip the cell asks for."""


def load_json(path: Path) -> dict:
    """A JSON file, parsed."""
    return json.loads(path.read_text())


def load_module(path: Path):
    """The Python file at ``path`` as a module of its own."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name: str) -> dict:
    """Everything a run of cell ``name`` reads, found by name: its entry,
    configuration, traffic mix, limits, end-to-end metrics, and the
    per-layer metrics listed for it with their readers' paths."""
    bm = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    listed = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return {
        "cell": cell,
        "config": load_json(ROOT / configs[cell["config"]]["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "limits": load_json(BENCH / "workloads" / f"{name}.json")["limits"],
        "end_to_end": [m for m in bm["end_to_end"] if listed(m)],
        "per_layer": [{**m, "reader": BENCH / "metrics" / f"{m['name']}.py"}
                      for m in bm["per_layer"] if listed(m)],
    }


def find_chips(chips: int):
    """The TPU devices, checked against the cell and the peaks table."""
    import jax

    from bench import counts

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, {len(devs)} found")
    try:
        peaks = counts.peaks(devs[0].device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return devs, peaks


def metric_values(spec: dict, out: dict, trace: bool) -> dict:
    """The cell's metrics of this run: its end-to-end ones from the mode,
    or its per-layer ones from their readers.  A reader that finds
    nothing to read returns None, and the metric is left out."""
    if not trace:
        return {m["name"]: {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}
                for m in spec["end_to_end"]}
    values = {}
    for m in spec["per_layer"]:
        value = load_module(m["reader"]).read(out["record"])
        if value is not None:
            values[m["name"]] = {"value": value, "unit": m["unit"]}
    return values


def result_line(spec: dict, out: dict, devs, trace: bool) -> dict:
    """The result object; ``checks`` comes last."""
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": spec["cell"]["chips"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metric_values(spec, out, trace),
            "device": device}
    if trace:
        red = out["record"]["trace"]
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    return line


def run(args, *, require_chip: bool = True, resize: dict | None = None,
        **hooks) -> dict:
    """One run of a cell; returns the result object.  ``require_chip``,
    ``resize`` (sizes that replace the configuration's) and ``hooks``
    (passed to the mode) serve the tests alone."""
    spec = cell_spec(args.workload)
    spec["config"].update(resize or {})
    if require_chip:
        devs, peaks = find_chips(spec["cell"]["chips"])
    else:
        import jax

        from bench import counts

        devs, peaks = jax.devices(), counts.peaks("TPU v5 lite")
    t_chips = time.perf_counter()
    mode = load_module(BENCH / "modes" / f"{spec['traffic']['mode']}.py")
    ctx = {"config": spec["config"], "traffic": spec["traffic"],
           "limits": spec["limits"], "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "device": devs[0], "peak": peaks,
           "t_start": T_START, "t_chips": t_chips, **hooks}
    out = mode.run(ctx)
    out["notes"]["cell"] = args.workload
    print(json.dumps(out["notes"]), file=sys.stderr)
    return result_line(spec, out, devs, bool(args.trace))


def parse(argv=None):
    """The command line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare() -> None:
    """Put the program and the benchmark on the path, and keep compiled
    programs in the checkout's cache (every program, however quick its
    compile), with the TPU runtime's transfer buffer at
    :data:`PREMAPPED_BYTES` unless the environment sets it."""
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    COMPILE_CACHE.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(PREMAPPED_BYTES))
    from repro.launch.backend import setup

    import jax

    setup()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    """Entry point; see the module's docstring."""
    args = parse(argv)
    try:
        prepare()
    except ImportError as e:
        print(f"bench: the program is not beside the benchmark ({e})", file=sys.stderr)
        return 2
    try:
        line = run(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
