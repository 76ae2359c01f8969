"""Import layering of the sparse stack.

The GCN/GAT path (``repro.models.layers`` → ``fuse`` → ``repro.sparse`` →
``kernels``) loads only what it runs: not the process set-up in
``repro.launch``, not the language-model configs, and not the
language-model families behind ``repro.models.registry``.  Each case
imports its entry point and runs one interpreted SpMM in a fresh
interpreter, then reads ``sys.modules``.
"""
import json
import os
import subprocess
import sys

import pytest

from conftest import SRC

FORBIDDEN = ("repro.launch", "repro.configs") + tuple(
    f"repro.models.{m}"
    for m in ("registry", "transformer", "encdec", "hybrid", "mamba2",
              "ssm_lm", "vlm"))

PROGRAM = """
import importlib, json, sys
importlib.import_module({entry!r})
import jax
from repro.sparse import Schedule, random_csr, spmm
a = random_csr(64, 64, density=0.1, seed=0)
b = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
spmm(a, b, schedule=Schedule("eb", nnz_tile=64, group_size=8)).block_until_ready()
print(json.dumps(sorted(m for m in sys.modules if m.startswith("repro"))))
"""


@pytest.mark.parametrize("entry", ["repro.models.layers", "repro.sparse"])
def test_sparse_path_loads_no_upper_layer(entry):
    env = {**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run([sys.executable, "-c", PROGRAM.format(entry=entry)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-4000:]
    loaded = json.loads(res.stdout.strip().splitlines()[-1])
    assert "repro.kernels.spmm_eb" in loaded
    above = [m for m in loaded
             if any(m == f or m.startswith(f + ".") for f in FORBIDDEN)]
    assert above == []
