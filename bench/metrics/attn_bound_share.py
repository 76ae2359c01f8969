"""``attn_bound_share``: the share of the traced window's additive-score
attention forwards whose softmax shift took the bound
(``attn.shift/bound``) rather than the exact row maximum
(``attn.shift/exact``).  The two are the branches of one conditional a
layer (``repro.kernels.fused_attention.additive_shift``), found in the
compiled step's HLO; each layer's conditional runs once a step.

The reduced trace keeps each operation's summed device time, not its
number of events.  So a branch's runs are read from one operation of it
that has a twin in the other branch: the same opcode and shape, and not
a fusion, whose shape says nothing of its work (in the compiled step,
the pad of the kernel's shift table that ends each branch), so the two
cost alike a run; the last such pair in the bound branch, else the last
pair of fusions, else each branch's first operation.  Events of async
copies time a wait, not a run, and are never markers.  Where only one
branch's operation ran in the window, that branch took every forward of
the layer; where both ran, the layer's forwards are split in proportion
to the two operations' time."""
from __future__ import annotations

import re

#: a conditional's branch computations, as the compiled HLO names them
BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
#: the branch a scope names, in an instruction's ``op_name``
KIND = re.compile(r'op_name="[^"]*attn\.shift\)*/(?:[^"]*/)?(bound|exact)[/")]')
#: opcodes that name values without running on the device
SILENT = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast",
          "after-all"}
#: opcodes whose events time a wait for an async copy, not a run
ASYNC = {"copy-start", "copy-done"}
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(")


def _instruction(line: str) -> tuple[str, str, str] | None:
    """``(name, shape, opcode)`` of an instruction line, the shape without
    its layouts; None for any other line."""
    head, sep, rest = line.strip().removeprefix("ROOT ").partition(" = ")
    if not sep:
        return None
    if rest.startswith("("):  # a tuple shape: up to its closing paren
        depth = 0
        for end, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = rest[:end + 1], rest[end + 1:]
    else:
        shape, _, rest = rest.partition(" ")
    shape = re.sub(r"\{[^{}]*\}", "", shape)
    return head.lstrip("%"), shape, rest.strip().partition("(")[0]


def _computations(hlo_text: str) -> dict:
    """Each computation's instruction lines, in program order."""
    out, body = {}, None
    for ln in hlo_text.splitlines():
        m = _HEADER.match(ln)
        if m and ln.rstrip().endswith("{"):
            body = out.setdefault(m.group(1), [])
        elif ln.startswith("}"):
            body = None
        elif body is not None and ln.strip():
            body.append(ln)
    return out


def shift_branches(hlo_text: str) -> list[dict]:
    """One dict a conditional whose branches are ``attn.shift/bound`` and
    ``attn.shift/exact``: each kind maps to its branch's operations that
    run on the device, in program order, each ``(name, shape, opcode)``."""
    comps = _computations(hlo_text)
    layers = []
    for ln in hlo_text.splitlines():
        m = BRANCHES.search(ln)
        if not m:
            continue
        found = {}
        for name in (c.strip().lstrip("%") for c in m.group(1).split(",")):
            body = comps.get(name, [])
            kinds = {k.group(1) for k in map(KIND.search, body) if k}
            ops = [op for op in map(_instruction, body) if op and op[2] not in SILENT]
            if len(kinds) == 1 and ops:
                found[kinds.pop()] = ops
        if set(found) == {"bound", "exact"}:
            layers.append(found)
    return layers


def markers(layer: dict) -> dict:
    """The operation of each branch whose time counts its runs: the
    bound's last with a twin in the exact branch that is not a fusion,
    and that twin; else the last pair of fusions; else each branch's
    first."""
    twins = {op[1:]: op[0] for op in layer["exact"] if op[2] not in ASYNC}
    pairs = [(name, twins[tuple(kind)], kind[1])
             for name, *kind in reversed(layer["bound"]) if tuple(kind) in twins]
    pairs.sort(key=lambda p: p[2] == "fusion")  # stable: fusions last
    if pairs:
        return {"bound": pairs[0][0], "exact": pairs[0][1]}
    return {k: ops[0][0] for k, ops in layer.items()}


def read(rec):
    """Percent of the forwards, or None without a trace, where the program
    takes no such shift, or where no branch ran in the window."""
    tr = rec["trace"]
    if not tr:
        return None
    shares = []
    for layer in shift_branches(rec["hlo"]):
        t = {k: tr["ops"].get(name, 0.0) for k, name in markers(layer).items()}
        ran = t["bound"] + t["exact"]
        if ran > 0.0:
            shares.append(t["bound"] / ran)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
