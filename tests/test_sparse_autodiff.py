"""Differentiable SpMM: custom-vjp (SDDMM backward) vs dense autodiff."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedule import Epilogue, Schedule
from repro.kernels import ref
from repro.sparse import random_csr, spmm
from repro.sparse.autodiff import make_spmm
from repro.sparse.formats import CSR
from repro.sparse.ops import _spmm_bwd


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_spmm_grads_match_dense(impl):
    csr = random_csr(24, 20, density=0.1, seed=0)
    coo = csr.tocoo()
    n_rows, n_cols = csr.shape
    b = jax.random.normal(jax.random.PRNGKey(0), (n_cols, 6))
    vals = coo.vals

    spmm_fn = make_spmm(coo.rows, coo.cols, n_rows, n_cols, impl=impl)
    tgt = jax.random.normal(jax.random.PRNGKey(1), (n_rows, 6))

    def loss_sparse(vals, b):
        return jnp.sum((spmm_fn(vals, b) - tgt) ** 2)

    def loss_dense(vals, b):
        dense = jnp.zeros((n_rows, n_cols)).at[coo.rows, coo.cols].set(vals)
        return jnp.sum((dense @ b - tgt) ** 2)

    l1, (dv1, db1) = jax.value_and_grad(loss_sparse, argnums=(0, 1))(vals, b)
    l2, (dv2, db2) = jax.value_and_grad(loss_dense, argnums=(0, 1))(vals, b)
    assert abs(float(l1) - float(l2)) < 1e-3
    np.testing.assert_allclose(np.asarray(dv1), np.asarray(dv2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(db1), np.asarray(db2),
                               rtol=1e-4, atol=1e-4)


def test_gcn_layer_trains_through_sparse():
    """One GCN aggregation layer optimized end-to-end via the sparse vjp."""
    csr = random_csr(16, 16, density=0.2, seed=3)
    coo = csr.tocoo()
    spmm_fn = make_spmm(coo.rows, coo.cols, 16, 16)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    y = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
    w = jnp.zeros((8, 4))

    def loss(w):
        return jnp.mean((spmm_fn(coo.vals, x @ w) - y) ** 2)

    g = jax.grad(loss)
    losses = []
    for _ in range(25):
        w = w - 0.1 * g(w)
        losses.append(float(loss(w)))
    assert losses[-1] < losses[0] * 0.9


def _relu_problem(with_bias):
    """A CSR with no empty row, an operand and a bias whose ReLU
    pre-activation has both signs and no exact zero."""
    csr = random_csr(40, 32, density=0.3, seed=5)
    kb, kbias, kd = jax.random.split(jax.random.PRNGKey(2), 3)
    b = jax.random.normal(kb, (32, 8))
    bias = jax.random.normal(kbias, (8,)) if with_bias else None
    dout = jax.random.normal(kd, (40, 8))
    coo = csr.tocoo()
    z = np.asarray(ref.spmm_coo_ref(coo.rows, coo.cols, coo.vals, b, 40))
    if with_bias:
        z = z + np.asarray(bias)
    assert (z > 0).any() and (z < 0).any() and (z != 0).all()
    return csr, b, bias, dout


@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("kernel", ["eb", "rb"])
def test_relu_backward_from_saved_output_matches_recompute(kernel, with_bias):
    """For a float32 ReLU, the backward that reads the forward's output
    gives the recompute's ``dB``, ``dbias`` and ``dvals`` bit for bit,
    called directly and through ``jax.grad`` (which reaches ``dvals`` on
    'eb' alone: 'rb' builds its ELL from concrete values)."""
    csr, b, bias, dout = _relu_problem(with_bias)
    coo = csr.tocoo()
    ep = Epilogue(activation="relu", bias=with_bias)
    sched = Schedule(kernel=kernel)

    def loss(vals, bb, bi):
        a = CSR(indptr=csr.indptr, indices=csr.indices, vals=vals,
                shape=csr.shape)
        return jnp.sum(spmm(a, bb, sched, bias=bi, epilogue=ep) * dout)

    argnums = (0, 1, 2) if with_bias else (0, 1)
    if kernel == "rb":
        argnums = argnums[1:]
    got = jax.grad(loss, argnums=argnums)(csr.vals, b, bias)
    out = spmm(csr, b, sched, bias=bias, epilogue=ep)

    def bwd(saved):
        dv, db, dbias, _ = _spmm_bwd(ep, coo.rows, coo.cols, csr.shape,
                                     coo.vals, b, bias, None, dout,
                                     dvals=True, out=saved)
        return (dv, db, dbias) if with_bias else (dv, db)

    recompute, from_out = bwd(None), bwd(out)
    for r, o in zip(recompute, from_out):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))
    # jax.grad's results are the tail of (dvals, dB[, dbias])
    for g, r in zip(got, recompute[len(recompute) - len(got):]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def _bwd_scatters(hlo_text):
    """Scatter instructions whose ``op_name`` lies under ``spmm.bwd``."""
    return sum(1 for ln in hlo_text.splitlines()
               if " scatter(" in ln
               and re.search(r'op_name="[^"]*spmm\.bwd', ln))


@pytest.mark.parametrize("ep, value_dtype, scatters", [
    (Epilogue(activation="relu", bias=True), None, 1),
    (Epilogue(activation="gelu", bias=True), None, 2),
    (Epilogue(activation="relu", bias=True, residual=True), None, 2),
    (Epilogue(activation="relu", bias=True, out_dtype="bfloat16"), None, 2),
    (Epilogue(activation="relu", bias=True), "bfloat16", 2),
], ids=["relu", "gelu", "relu-residual", "relu-bf16-out", "relu-bf16-values"])
def test_backward_recomputes_only_where_the_output_cannot_say(
        ep, value_dtype, scatters):
    """The gradient's lowered HLO holds one scatter under ``spmm.bwd``
    (the transpose SpMM) where a float32 ReLU's output gives its
    derivative, and two (the recompute too) everywhere else."""
    csr, b, bias, dout = _relu_problem(True)
    res = jnp.ones((40, 8)) if ep.residual else None
    sched = Schedule(value_dtype=value_dtype)

    def loss(bb):
        y = spmm(csr, bb, sched, bias=bias, residual=res, epilogue=ep)
        return jnp.sum(y.astype(jnp.float32) * dout)

    hlo = jax.jit(jax.grad(loss)).lower(b).as_text(dialect="hlo",
                                                  debug_info=True)
    assert _bwd_scatters(hlo) == scatters
