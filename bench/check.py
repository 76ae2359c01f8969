"""The comparison that decides ``correct`` for a training cell.

The numbers compared, each against its limit (``bench/workloads/<cell>.json``):

``loss``      the relative gap between the program's and the reference's
              loss of the first step;
``grad``      the first gradient, as the optimizer got it, by the worst
              leaf: the gap between the two norms of a leaf, over the
              reference's norm of that leaf or of the median leaf,
              whichever is larger;
``update``    the parameters' change in the first step, by the same
              worst-leaf measure.  Leaves whose reference gradient is
              under a thousandth of the median leaf's are left out: Adam
              moves them by round-off alone;
``loss_3``    the largest relative gap of the losses of all the first
              steps;
``update_3``  the parameters' change over all the first steps, by the
              worst-leaf measure of ``update``: what the optimizer carried
              from step to step.

The first step's numbers are tight.  Those over the first steps have
room for a ReLU unit that rounding turns on in one run and off in the
other, which Adam, normalising each element's step, turns into a large
change of the update for elements whose gradient is small (PERF.md,
Findings).
"""
from __future__ import annotations

import numpy as np

NEGLIGIBLE_GRAD = 1e-3  # share of the median leaf's gradient norm


def _norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in tree.items()}


def worst_leaf_gap(got: dict, want: dict, leaves=None) -> float:
    """Largest ``|‖got‖ - ‖want‖| / max(‖want‖, median leaf ‖want‖)``."""
    gn, wn = _norms(got), _norms(want)
    floor = float(np.median(list(wn.values())))
    keys = sorted(wn) if leaves is None else sorted(leaves)
    gaps = [abs(gn[k] - wn[k]) / max(wn[k], floor, np.finfo(np.float32).tiny)
            for k in keys]
    return max(gaps)


def moved_leaves(grad: dict) -> list:
    """Leaves whose reference gradient is not nought to rounding."""
    n = _norms(grad)
    med = float(np.median(list(n.values())))
    return [k for k, v in n.items() if v >= NEGLIGIBLE_GRAD * med]


def _change(snap: dict, after: str) -> dict:
    return {k: snap[after][k] - snap["params0"][k] for k in snap["params0"]}


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers of a program run against the reference run, each a
    dict of first steps (:func:`bench.reference.first_steps`); a loss
    that is not finite reads as infinitely far."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    gaps = np.abs(lp - lr) / np.abs(lr)
    gaps[~np.isfinite(lp)] = np.inf
    moved = moved_leaves(ref["grad1"])
    return {
        "loss": float(gaps[0]),
        "grad": worst_leaf_gap(prog["grad1"], ref["grad1"]),
        "update": worst_leaf_gap(_change(prog, "params1"),
                                 _change(ref, "params1"), moved),
        "loss_3": float(np.max(gaps)),
        "update_3": worst_leaf_gap(_change(prog, "params_end"),
                                   _change(ref, "params_end"), moved),
    }


def verdict(nums: dict, lims: dict) -> tuple[bool, dict]:
    """Whether every number is within its limit (a NaN is not), and the
    numbers beside their limits; every number has a limit."""
    if set(nums) != set(lims):
        raise KeyError(f"numbers {sorted(nums)} but limits {sorted(lims)}")
    shown = {k: {"value": nums[k], "limit": lims[k]} for k in lims}
    ok = all(bool(nums[k] <= lims[k]) for k in lims)
    return ok, shown
