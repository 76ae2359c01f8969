"""Differentiable SpMM — the backward pass closes the paper's algebra
family on itself:

    out = A @ B            (SpMM,  Eq. 2d)
    dvals = SDDMM(dOut, B) (Eq. 2c: dA[i,j] = <dOut[i,:], B[j,:]>)
    dB    = Aᵀ @ dOut      (SpMM with rows/cols swapped — unsorted row
                            stream, which the segment-group kernel
                            handles by opening extra runs)

``make_spmm`` closes over the (static) sparsity pattern and returns a
custom-vjp function of (vals, b), so GNN training differentiates through
the same kernels the forward uses.
"""
from __future__ import annotations

import jax

from ..kernels import ref


def make_spmm(rows, cols, n_rows: int, n_cols: int, *, impl: str = "ref",
              schedule=None):
    """Returns spmm_fn(vals, b) -> (n_rows, b.shape[1]) differentiable in
    vals and b. rows/cols: (nnz,) int32 (row-sorted preferred)."""

    def _fwd_impl(vals, b):
        if impl == "pallas":
            from ..core.schedule import Schedule, as_schedule
            from ..kernels.ops import spmm as kspmm
            from .formats import GroupedCOO

            sched = (as_schedule(schedule) if schedule is not None
                     else Schedule("eb", nnz_tile=64, col_tile=8,
                                   group_size=8))
            g = GroupedCOO(rows=rows, cols=cols, vals=vals,
                           shape=(n_rows, n_cols), nnz=vals.shape[0],
                           nnz_tile=vals.shape[0])
            return kspmm(g, b, sched)
        return ref.spmm_coo_ref(rows, cols, vals, b, n_rows)

    @jax.custom_vjp
    def _spmm_fn(vals, b):
        return _fwd_impl(vals, b)

    def _fwd(vals, b):
        return _fwd_impl(vals, b), (vals, b)

    def _bwd(res, dout):
        vals, b = res
        # dA values: sampled dense-dense product at the sparsity pattern
        dvals = ref.sddmm_ref(rows, cols, dout, b).astype(vals.dtype)
        # dB: transpose SpMM (cols become the segment ids)
        db = ref.spmm_coo_ref(cols, rows, vals, dout, n_cols).astype(b.dtype)
        return dvals, db

    _spmm_fn.defvjp(_fwd, _bwd)
    return _spmm_fn
