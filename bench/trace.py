"""Reduce a profiler trace of the measured window to device numbers.

A ``--trace 1`` run writes the profiler's ``.xplane.pb``; this module
reads it with ``jax.profiler.ProfileData`` alone.  On a TPU the device
plane (``/device:TPU:<i>``) holds the line ``XLA Ops``, one event per
HLO operation run, named by the operation's HLO text
(``%<instruction> = <shape> <opcode>(...)``), and the line
``XLA Modules``, one event per run of a compiled program.  The host
plane holds the benchmark's own span ``bench.window`` around the traced
steps.  The device and host clocks of one trace are not aligned to the
millisecond, so the window's length comes from the host span and every
device number from the device plane alone.

Reduction, per device plane, then averaged over the planes:

* busy: the union of the ``XLA Ops`` intervals (the long transfers of
  the ``Async XLA Ops`` line overlap compute and are not counted);
* per-operation time: the sum of each instruction's event durations;
* idle time, by what held the device back: ``between steps`` where no
  program runs (the host returns from one step's wait and dispatches the
  next), ``in step`` between the operations of a program, and ``window
  edges`` from the window's start to the first operation and from the
  last one to the window's end.
"""
from __future__ import annotations

import bisect
import collections
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BETWEEN_STEPS, IN_STEP, EDGES = "between steps", "in step", "window edges"
TOP = 10  # entries of each breakdown list
#: Kernels of the SpMM, as the program names their jitted wrappers
SPMM_KERNELS = ("spmm_eb", "spmm_rb")


def op_name(event_name: str) -> str:
    """The HLO instruction name of an ``XLA Ops`` event."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def pallas_launches(hlo_text: str) -> list[dict]:
    """The Pallas kernel launches (``tpu_custom_call``) of a compiled
    program's HLO text, in program order.  Each is a dict: ``name``, the
    instruction name its trace events carry; ``kernel``, the one of
    :data:`SPMM_KERNELS` that the launch's instruction name, ``op_name``
    metadata (the jitted wrapper: ``jvp(jit(spmm_eb))``) or kernel name
    holds, None where none does; ``backward``, whether its ``op_name``
    lies under ``transpose(``, the backward pass."""
    out = []
    for ln in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in ln:
            continue
        ops = re.findall(r'op_name="([^"]*)"', ln)
        names = [op_name(ln), *ops, *re.findall(r'"name":"([^"]*)"', ln)]
        kernel = next((k for k in SPMM_KERNELS if any(k in n for n in names)), None)
        out.append({"name": op_name(ln), "kernel": kernel,
                    "backward": any("transpose(" in o for o in ops)})
    return out


def _union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _inside(t, spans) -> bool:
    """Whether ``t`` lies in one of the sorted, disjoint ``spans``."""
    i = bisect.bisect_right(spans, (t, float("inf"))) - 1
    return i >= 0 and spans[i][0] <= t < spans[i][1]


def reduce(profile) -> dict:
    """Window length, mean device busy time, per-operation device time
    and idle gaps by label, all in seconds, from a ``ProfileData``."""
    window_s, devices = None, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW_SPAN:
                        window_s = ev.duration_ns * 1e-9
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    if window_s is None:
        raise ValueError(f"the trace holds no host span {WINDOW_SPAN!r}")
    ops, gaps = collections.Counter(), collections.Counter()
    busy = 0.0
    for lines in devices:
        evs = sorted(lines.get(OPS_LINE, []), key=lambda e: e.start_ns)
        modules = sorted((e.start_ns, e.end_ns) for e in lines.get(MODULES_LINE, []))
        for ev in evs:
            ops[op_name(ev.name)] += ev.duration_ns * 1e-9
        merged = _union((e.start_ns, e.end_ns) for e in evs)
        busy += sum(e - s for s, e in merged) * 1e-9
        for (_, end), (nxt, _) in zip(merged, merged[1:]):
            label = IN_STEP if _inside((end + nxt) / 2, modules) else BETWEEN_STEPS
            gaps[label] += (nxt - end) * 1e-9
        if merged:
            gaps[EDGES] += window_s - (merged[-1][1] - merged[0][0]) * 1e-9
    n = len(devices)
    return {"window_s": window_s, "busy_s": busy / n, "devices": n,
            "ops": {k: v / n for k, v in ops.items()},
            "gaps": {k: v / n for k, v in gaps.items()}}


def find_xplane(logdir) -> Path:
    """The one ``.xplane.pb`` the profiler wrote under ``logdir``."""
    found = sorted(Path(logdir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {logdir}, found {found}")
    return found[0]


def reduce_file(path) -> dict:
    """:func:`reduce` of a recorded ``.xplane.pb``."""
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(path)))


def reduce_dir(logdir) -> dict:
    """:func:`reduce` of the trace under ``logdir``."""
    return reduce_file(find_xplane(logdir))


def breakdown(red: dict) -> dict:
    """The operations that took most device time and the idle time by
    label, each at most :data:`TOP` ``[name, seconds]`` pairs."""
    top = lambda d: [[k, v] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"device_ops": top(red["ops"]), "idle_gaps": top(red["gaps"])}


def spmm_seconds(red: dict, launches: list) -> float:
    """Device time of the SpMM kernel launches among ``launches``
    (:func:`pallas_launches`) in the window."""
    return sum(red["ops"].get(lc["name"], 0.0) for lc in launches if lc["kernel"])


def xla_seconds(red: dict, launches: list) -> float:
    """Device time of the operations that are not Pallas launches."""
    pallas = {lc["name"] for lc in launches}
    return sum(v for k, v in red["ops"].items() if k not in pallas)
