"""The harness end to end on the CPU at a cut size: it refuses to run
without a chip; a sound run is correct; the control and each fault a
training cell can have, planted under the timed path (the optimizer's
state not carried from step to step among them), make ``correct``
false."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from bench.run import ROOT, parse, run
from bench.traffic.gcn import propagate

CELL = "gcn-cora.train"
CUT = dict(n_nodes=300, n_edges=700, n_entries=1700, n_features=40)


def cut_run(**hooks):
    """One run of the cell at a cut size, with no look for a chip."""
    args = parse(["--workload", CELL, "--seed", str(2**31 + 17),
                  "--seconds", "0.2", "--trace", "0"])
    return run(args, require_chip=False, resize=CUT, **hooks)


def test_refuses_without_a_chip():
    """Refuses without a chip."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    with pytest.raises(json.JSONDecodeError):
        json.loads(last)


def test_refuses_without_the_program(tmp_path):
    """Refuses in a directory that holds only the benchmark."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert proc.returncode != 0
    assert "program is not beside" in proc.stderr
    assert proc.stdout.strip() == ""


def test_sound_run_is_correct():
    """Sound run is correct."""
    line = cut_run()
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"step_ms", "step_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"loss", "grad", "update", "loss_3", "update_3"}
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


def _unchanged_state(step):
    def broken(p, s, *args):
        return p, s, step(p, s, *args)[2]
    return broken


def _fresh_moments(step):
    def broken(p, s, *args):
        zero = jax.tree.map(jnp.zeros_like, s.mu)
        return step(p, s._replace(mu=zero, nu=zero), *args)
    return broken


def _fresh_state(step):
    def broken(p, s, *args):
        return step(p, jax.tree.map(jnp.zeros_like, s), *args)
    return broken


def _half_batch(step):
    def broken(p, s, x, y, train, key):
        return step(p, s, x, y, train[::2], key)
    return broken


def _altered_logits(*args, **kw):
    from repro.models.layers import gcn_two_layer

    return gcn_two_layer(*args, **kw).at[:, 0].add(0.05)


def _control(adj, x, w0, w1, b0, b1, *, schedule=None):
    """The reference's forward, its dense products at precision high."""
    n = adj.shape[0]
    rows = jnp.asarray(np.repeat(np.arange(n), np.diff(np.asarray(adj.indptr))))
    z = propagate(rows, adj.indices, adj.vals, reference.dot_bf16x3(x, w0), n) + b0
    h = jnp.maximum(z, 0.0)
    return propagate(rows, adj.indices, adj.vals, reference.dot_bf16x3(h, w1), n) + b1


@pytest.mark.parametrize("hooks", [
    {"wrap_step": _unchanged_state},
    {"wrap_step": _half_batch},
    {"gcn": _altered_logits},
    {"gcn": _control},
    {"wrap_step": _fresh_moments},
    {"wrap_step": _fresh_state},
], ids=["unchanged_state", "half_batch", "altered_logits", "control",
        "fresh_moments", "fresh_state"])
def test_fault_under_the_timed_path_is_not_correct(hooks):
    """Fault under the timed path is not correct."""
    line = cut_run(**hooks)
    assert line["correct"] is False, line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
