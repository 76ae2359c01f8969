"""Attribute a traced window to the program's layers: the device's XLA
time by program scope, and the idle time between steps by what the host
was doing in it.

Scopes.  The program names its device work with ``jax.named_scope``;
the names reach each compiled instruction's ``op_name`` metadata (a
fusion carries its root's), nested outer to inner, and under autodiff
wrapped as ``jvp(...)`` or ``transpose(jvp(...))``.  The scopes read
here are :data:`SCOPE`'s: ``fuse.launch<i>.<kind>`` (one planned launch,
its dense product and its backward), ``spmm.bwd`` with the children
``recompute``, ``act``, ``sddmm``, ``tspmm`` and ``dbias`` (the sparse
VJP's backward), and ``optimizer``.  Each non-Pallas operation of the
trace goes to the innermost of them in its instruction's ``op_name``,
or to :data:`UNSCOPED`.

Host phases.  The host plane holds the runtime's own events
(:data:`EVENTS`).  The device plane's clock is not the host's: on a TPU
v5e its programs start more than a millisecond before the host call
that launched them on the raw clocks.  So the offset is bracketed by
causality: a program cannot start before the host began launching it,
and the host's read of its completion flag cannot end before it ended.
The offset is taken at the launch bound (a launch reaches the device
within microseconds; completion is read up to half a millisecond late),
and the bracket's width is kept beside it: that much of the first phase
may be clock error rather than waiting.  The profiler gives no
synchronised device clock here (the device events carry only
``device_offset_ps``).  Where an event is missing or the bounds cross,
the phases read None.
"""
from __future__ import annotations

import bisect
import collections
import re
import statistics

from bench import trace

#: the program's scope names as they appear in ``op_name``, innermost last
SCOPE = re.compile(r"(?<![^/(])(fuse\.launch\d+\.\w+|optimizer"
                   r"|spmm\.bwd(?:/(?:recompute|act|sddmm|tspmm|dbias))?)(?![^/)])")
UNSCOPED = "unscoped"
SPMM_BWD = "spmm.bwd"
#: the host events the phases rely on: the TPU runtime's and jaxlib's
#: own trace events (never the Python tracer's ``$...`` events); the
#: call is matched by prefix, the rest by name
EVENTS = {"call": "PjitFunction(",
          "execute": "PJRT_LoadedExecutable_Execute",
          "launch": "TpuLoadedExecutable::ExecuteLaunch",
          "read": "ReadSyncFlag",
          "done": "tpu::System::Execute=>Done"}
#: the phases of one gap between steps, in order: device end, the
#: completion flag's read, the runtime's completion handling, Python
#: until the next call, the call's argument handling, the runtime's
#: execute call, and from its return to the device's start
PHASES = ("until_read", "completion", "python", "dispatch", "execute", "launch")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")


def scope_of(op_name: str) -> str:
    """The innermost program scope in an ``op_name`` (the first of a
    merged ``a;b``), ``spmm.bwd/act`` written ``spmm.bwd.act``."""
    found = SCOPE.findall(op_name.split(";", 1)[0])
    return found[-1].replace("/", ".") if found else UNSCOPED


def instruction_scopes(hlo_text: str) -> dict:
    """Each instruction of a compiled program's HLO text that carries an
    ``op_name``, mapped to its scope."""
    out = {}
    for ln in hlo_text.splitlines():
        m, op = _INSTRUCTION.match(ln), re.search(r'op_name="([^"]*)"', ln)
        if m and op:
            out[m.group(1)] = scope_of(op.group(1))
    return out


def xla_by_scope(red: dict, hlo_text: str, launches: list) -> dict:
    """Device seconds of the operations that are not Pallas launches
    (:func:`bench.trace.pallas_launches`), summed by scope; the values
    sum to :func:`bench.trace.xla_seconds`."""
    scopes = instruction_scopes(hlo_text)
    pallas = {lc["name"] for lc in launches}
    out = collections.Counter()
    for name, secs in red["ops"].items():
        if name not in pallas:
            out[scopes.get(name, UNSCOPED)] += secs
    return dict(out)


def spmm_bwd_seconds(by_scope: dict) -> float:
    """Device seconds under ``spmm.bwd`` and its children."""
    return sum(v for k, v in by_scope.items()
               if k == SPMM_BWD or k.startswith(SPMM_BWD + "."))


def _host_events(profile) -> dict:
    """``kind -> [(start_ns, end_ns)]`` of :data:`EVENTS` over the host
    planes, sorted; a call nested in another call is left out."""
    found = {k: [] for k in EVENTS}
    by_name = {name: kind for kind, name in EVENTS.items() if kind != "call"}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            calls_end = -1.0
            for ev in sorted(ln.events, key=lambda e: (e.start_ns, -e.duration_ns)):
                kind = by_name.get(ev.name)
                if ev.name.startswith(EVENTS["call"]):
                    if ev.start_ns < calls_end:
                        continue
                    calls_end, kind = ev.end_ns, "call"
                if kind:
                    found[kind].append((ev.start_ns, ev.end_ns))
    return {k: sorted(v) for k, v in found.items()}


def _device(profile):
    """The first device plane's programs and merged operation intervals."""
    planes = sorted((p for p in profile.planes if trace.DEVICE_PLANE.match(p.name)),
                    key=lambda p: p.name)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    lines = {ln.name: list(ln.events) for ln in planes[0].lines}
    modules = sorted((e.start_ns, e.end_ns) for e in lines.get(trace.MODULES_LINE, []))
    ops = trace._union((e.start_ns, e.end_ns) for e in lines.get(trace.OPS_LINE, []))
    return modules, ops


def _host_steps(ev: dict) -> list | None:
    """One dict a host call: its start, its execute call, the runtime's
    launch, and the completion read and handling before the next call;
    None where an event is missing from a step."""
    calls = ev["call"]
    steps = []
    for i, (c0, _) in enumerate(calls):
        c1 = calls[i + 1][0] if i + 1 < len(calls) else float("inf")
        within = {k: [e for e in ev[k] if c0 <= e[0] < c1] for k in EVENTS if k != "call"}
        if not all(within.values()):
            return None
        done = within["done"][-1]
        reads = [e for e in within["read"] if e[0] <= done[1]]
        if not reads:
            return None
        steps.append({"call": c0, "execute": within["execute"][0],
                      "launch": within["launch"][0], "read": reads[-1], "done": done})
    return steps


def host_phases(profile) -> dict:
    """The host's work of each step and of each gap between steps, from a
    ``ProfileData``, in seconds:

    * ``dispatch_s``: mean over steps of the call's start to the return
      of its execute call; ``completion_s``: mean of the completion
      flag's read to the end of the runtime's completion handling (both
      on the host clock alone);
    * ``clock_offset_s``: host minus device clock, at the launch bound;
      ``clock_bracket_s``: the causal bracket's width;
    * ``gap_phases_s``: each of :data:`PHASES` summed over the gaps
      between steps (as :func:`bench.trace.reduce` labels them) on the
      aligned clock; they sum to ``between_steps_s``.

    Every value is None where the events it needs are missing, or where
    the host's calls and the device's programs do not pair one to one;
    the offset and the phases are None where the bounds cross (the
    bracket then reads negative)."""
    out = dict.fromkeys(("dispatch_s", "completion_s", "clock_offset_s",
                         "clock_bracket_s", "gap_phases_s", "between_steps_s"))
    modules, ops = _device(profile)
    steps = _host_steps(_host_events(profile))
    if not steps:
        return out
    ns = 1e-9
    out["dispatch_s"] = statistics.fmean(s["execute"][1] - s["call"] for s in steps) * ns
    out["completion_s"] = statistics.fmean(s["done"][1] - s["read"][0] for s in steps) * ns
    if len(steps) != len(modules):
        return out
    lo = max(s["launch"][0] - m[0] for s, m in zip(steps, modules))
    hi = min(s["read"][1] - m[1] for s, m in zip(steps, modules))
    out["clock_bracket_s"] = (hi - lo) * ns
    if hi < lo:
        return out
    out["clock_offset_s"] = lo * ns
    phases = dict.fromkeys(PHASES, 0.0)
    between = 0.0
    starts = [m[0] for m in modules]
    for (_, g0), (g1, _) in zip(ops, ops[1:]):
        if trace._inside((g0 + g1) / 2, modules):
            continue
        k = max(0, min(len(steps) - 2, bisect.bisect_right(starts, g0) - 1))
        prev, nxt = steps[k], steps[k + 1]
        marks = [g0 + lo, prev["read"][0], prev["done"][1], nxt["call"],
                 nxt["execute"][0], nxt["execute"][1], g1 + lo]
        for i in range(1, len(marks)):
            marks[i] = min(max(marks[i], marks[i - 1]), marks[-1])
        for name, a, b in zip(PHASES, marks, marks[1:]):
            phases[name] += (b - a) * ns
        between += (g1 - g0) * ns
    out["gap_phases_s"], out["between_steps_s"] = phases, between
    return out
