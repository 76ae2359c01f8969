"""The single public sparse API: schedule coercion + kernel dispatch.

``spmm``, ``sddmm``, ``segment_reduce`` and ``sparse_attention`` all
accept ``schedule=`` as a name ('EB+PR', ...), a
:class:`~repro.core.schedule.Schedule`, an
:class:`~repro.core.AtomicParallelism` point, or a
:class:`~repro.core.SegmentGroup`.  ``spmm`` additionally accepts
``'auto'`` (the data-aware selector — the paper's Table-5 "dynamic
choice" made a library default); the other ops have no matrix to derive
statistics from, so ``'auto'`` raises there.

Fusion surface (DESIGN.md §8; *planned* multi-op fusion lives in
``repro.fuse`` — DESIGN.md §10 — which lowers chain nodes onto these
ops' epilogue slots rather than callers picking per-op):

* ``spmm(..., bias=, residual=, epilogue=)`` fuses the dense epilogue of
  a GCN-style layer (``act(A @ XW + b) [+ res]``) into the kernel's last
  reduction grid step — one kernel instead of three HBM passes.  The
  epilogue spec is auto-derived from the arrays you pass (or taken from
  ``schedule.epilogue`` / an explicit ``epilogue=``).
* ``segment_reduce(..., op="max"|"mean")`` runs the monoid-generalized
  group machinery (graph pooling); ``mean`` is the add monoid with a
  fused count column (one kernel pass + a divide).
* ``sparse_attention`` is the fused score → segment softmax → SpMM
  kernel (``kernels.fused_attention``; one weighted-sum pass behind
  a bound on the additive score's row max, a row-max pass first for the
  dot score), all heads in one launch, with a dot-product or
  GAT's additive score, CSR stored values as an additive score bias and
  an optional keep mask on the coefficients.

``spmm`` over CSR and ``sparse_attention`` are differentiable: forwards
run the scheduled Pallas kernels; ``spmm``'s backward closes the paper's
algebra family on itself (SDDMM / transpose-SpMM / segment ops — Sgap
Eq. 2c/2d) through the pure-JAX oracles, while ``sparse_attention``'s
backward is itself a fused Pallas kernel (DESIGN.md §9): one launch
recomputes the probabilities from the saved softmax row stats and
scatters dQ by row and dK/dV by column.
Feed-format conversions go through the
per-(format, tile) caches on ``CSR``/``GroupedCOO``, so serving loops
re-using the same matrix do not re-convert every call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.schedule import Epilogue, Schedule, as_schedule
from ..kernels import ops as kops
from ..kernels import ref
from ..kernels.fused_attention import (
    additive_shift,
    fused_sparse_attention as _fused_attn_fwd,
)
from ..kernels.fused_attention import (
    fused_sparse_attention_bwd as _fused_attn_bwd,
)
from ..kernels.fused_attention import sparse_attention_ref
from ..kernels.segment_reduce import segment_reduce as _segment_reduce_kernel
from .formats import CSR, ELL, GroupedCOO, QuantizedCSR, round_up
from .random import matrix_stats

__all__ = ["spmm", "sddmm", "segment_reduce", "sparse_attention"]


def _resolve_schedule(a, b, schedule, epilogue: Epilogue | None = None):
    if isinstance(schedule, str) and schedule in ("auto", "tune"):
        if isinstance(a, QuantizedCSR):
            # already-quantized input: the dtype axis is decided (int8);
            # select tiling from the inner pattern's statistics
            sched = Schedule.auto(matrix_stats(a.csr), int(b.shape[1]))
        elif not isinstance(a, CSR):
            # no CSR to derive statistics (or a fingerprint) from
            sched = Schedule("eb")
        elif schedule == "tune":
            from ..tune import tune_schedule

            return tune_schedule(a, int(b.shape[1]),
                                 epilogue=epilogue).schedule
        else:
            sched = Schedule.auto(matrix_stats(a), int(b.shape[1]))
    else:
        sched = as_schedule(schedule)
    if epilogue is not None:
        sched = sched.replace(epilogue=epilogue)
    return sched


def _derive_epilogue(schedule, epilogue, bias, residual) -> Epilogue | None:
    """Effective epilogue: an explicit ``epilogue=`` wins, else the
    schedule's own; the bias/residual flags are auto-set from the arrays
    actually passed (so ``spmm(..., bias=b)`` just works)."""
    import dataclasses

    ep = epilogue
    if ep is None and isinstance(schedule, Schedule):
        ep = schedule.epilogue
    if ep is None:
        ep = Epilogue()
    if bias is not None and not ep.bias:
        ep = dataclasses.replace(ep, bias=True)
    if residual is not None and not ep.residual:
        ep = dataclasses.replace(ep, residual=True)
    return None if ep.is_noop else ep


def spmm(a, b, schedule="auto", *, bias=None, residual=None,
         epilogue: Epilogue | None = None, impl: str = "pallas"):
    """out = epilogue(A @ B) for sparse A (CSR / GroupedCOO / ELL) and
    dense B.

    schedule    'auto' | 'tune' | name | Schedule | AtomicParallelism |
                SegmentGroup.  'tune' measures the top schedule
                candidates for this matrix (replaying the persistent
                fingerprint cache when it can — see ``repro.tune``);
                tuning is epilogue-aware (the fused work is measured).
    bias        (N,) fused bias-row add over output columns.
    residual    (n_rows, N) fused post-activation residual add.
    epilogue    explicit :class:`~repro.core.Epilogue` (activation /
                out_dtype); bias/residual flags are auto-derived from
                the arrays above.
    impl        'pallas' (scheduled kernel) or 'ref' (pure-jnp oracle).

    The CSR + pallas path is differentiable in ``a.vals``, ``b``,
    ``bias`` and ``residual``.  Narrow float ``value_dtype`` schedules
    (DESIGN.md §13) stay differentiable in all four — the forward moves
    the cast storage, the backward is the f32 ref path (straight-through
    w.r.t. the cast).  The int8 quantized path (``value_dtype='int8'``
    or a :class:`QuantizedCSR` input) is differentiable in ``b``/
    ``bias``/``residual`` only: quantization is a host-side calibration
    pass over concrete values, so ``a.vals`` is data there, not an
    operand.
    """
    ep = _derive_epilogue(schedule, epilogue, bias, residual)
    sched = _resolve_schedule(a, b, schedule, epilogue=ep)
    if impl != "ref":
        if isinstance(a, QuantizedCSR):
            return _spmm_quant_diff(a, b, sched, bias, residual)
        if isinstance(a, CSR):
            if sched.value_dtype == "int8":
                return _spmm_quant_diff(a.quantized(), b, sched, bias,
                                        residual)
            return _spmm_csr_diff(a, b, sched, bias, residual)
    return kops.spmm(a, b, sched, bias=bias, residual=residual, impl=impl)


def _spmm_csr_diff(a: CSR, b, sched: Schedule,
                   bias=None, residual=None):
    """Custom-VJP wrapper: scheduled (epilogued) kernel forward, ref
    backward.  ``y = act(A@B + bias) + residual`` (then dtype cast), so

        dz        = dy ⊙ act'(A@B + bias)      (VJP of the activation)
        dvals     = SDDMM(dz, B)               (Eq. 2c)
        dB        = Aᵀ · dz                    (Eq. 2d)
        dbias     = Σ_rows dz
        dresidual = dy

    The forward saves its output ``y`` with the operands.  For a
    float32 ReLU with no residual, over float32 value storage, ``y > 0``
    is ``act'`` itself and the backward takes it from there; every
    other epilogue recomputes ``A@B + bias`` (see :func:`_spmm_bwd`).

    Where the eb launch's blocks exceed VMEM
    (``kernels.ops.eb_stream_windows``), the forward runs the windowed
    launch over ``a.blocked(...)``, its values placed by the layout's
    ``positions`` (once, where ``a.vals`` is concrete); the backward is
    the same.
    """
    ep = sched.epilogue
    coo = a.tocoo()  # cached on the CSR instance
    rows, cols = coo.rows, coo.cols

    windows = (kops.eb_stream_windows(a.shape, int(b.shape[1]), sched)
               if sched.kernel == "eb" else None)
    if windows is not None:
        blk0 = a.blocked(sched.nnz_tile, windows)
        pos = blk0.positions()
        # concrete values are no operand anyone differentiates: the
        # memoized layout's own copy stands for them, placed once
        fixed = not isinstance(a.vals, jax.core.Tracer)

        def run(vals, bb, bias_x, res_x):
            blk = blk0 if fixed else blk0.with_vals(
                jnp.zeros((blk0.nnz_padded,), vals.dtype).at[pos].set(vals))
            return kops.spmm(blk, bb, sched, bias=bias_x, residual=res_x)
    elif sched.kernel == "eb":
        g0 = a.grouped(sched.nnz_tile, group_size=sched.group_size,
                       split_threshold=sched.split_threshold,
                       merge_threshold=sched.merge_threshold)
        if g0.skew is not None:
            # skew layout interleaves padding, so fresh vals are placed
            # by the memoized scatter index rather than a trailing pad
            pos = g0.skew_positions()

            def run(vals, bb, bias_x, res_x):
                vpad = jnp.zeros((g0.nnz_padded,),
                                 vals.dtype).at[pos].set(vals)
                g = GroupedCOO(rows=g0.rows, cols=g0.cols, vals=vpad,
                               shape=g0.shape, nnz=g0.nnz,
                               nnz_tile=g0.nnz_tile, skew=g0.skew)
                return kops.spmm(g, bb, sched, bias=bias_x,
                                 residual=res_x)
        else:
            pad = g0.nnz_padded - g0.nnz

            def run(vals, bb, bias_x, res_x):
                vpad = jnp.concatenate(
                    [vals, jnp.zeros((pad,), vals.dtype)]) if pad else vals
                g = GroupedCOO(rows=g0.rows, cols=g0.cols, vals=vpad,
                               shape=g0.shape, nnz=g0.nnz,
                               nnz_tile=g0.nnz_tile)
                return kops.spmm(g, bb, sched, bias=bias_x,
                                 residual=res_x)
    else:
        ell0 = a.ell(row_tile=sched.row_tile)
        rid, pos = a.ell_scatter_index()

        def run(vals, bb, bias_x, res_x):
            evals = jnp.zeros(ell0.vals.shape,
                              vals.dtype).at[rid, pos].set(vals)
            e = ELL(cols=ell0.cols, vals=evals, shape=ell0.shape,
                    width=ell0.width)
            return kops.spmm(e, bb, sched, bias=bias_x, residual=res_x)

    @jax.custom_vjp
    def _fn(vals, bb, bias_x, res_x):
        return run(vals, bb, bias_x, res_x)

    def _fwd(vals, bb, bias_x, res_x):
        out = run(vals, bb, bias_x, res_x)
        # a narrow-storage forward is not the f32 function the backward
        # differentiates, so its output cannot stand for the f32 one's
        saved = out if sched.value_dtype is None else None
        return out, (vals, bb, bias_x, res_x, saved)

    def _bwd(res, dout):
        vals, bb, bias_x, res_x, out = res
        return _spmm_bwd(ep, rows, cols, a.shape, vals, bb, bias_x, res_x,
                         dout, dvals=True, out=out)

    _fn.defvjp(_fwd, _bwd)
    return _fn(a.vals, b, bias, residual)


def _spmm_bwd(ep: Epilogue, rows, cols, shape, vals, bb, bias_x, res_x,
              dout, *, dvals: bool, out=None):
    """The reference backward of ``y = act(A@B + bias) + residual`` over
    A's COO pattern, under the scope ``spmm.bwd`` with each part in a
    child scope of its own, so that a trace splits it.  Returns
    ``(dvals, dB, dbias, dresidual)``; ``dvals`` is None unless asked
    for.

    ``out`` is the forward's output ``y`` where the forward computed the
    float32 function this differentiates (None otherwise).  Where it is
    float32 and the epilogue is ReLU with no residual, ``y > 0`` exactly
    where ``z = A@B + bias > 0``, so ``dz = dy`` there and 0 elsewhere:
    a compare and a select in place of recomputing ``z`` (a gather and a
    scatter over the nonzeros).  At an exact tie ``z == 0`` this gives
    0 (PyTorch's convention), where ``jnp.maximum``'s gradient gives
    0.5; at every other ``z`` the two are the same.  Every other epilogue (GELU, SiLU, tanh,
    sigmoid, a residual, a narrowed output) recomputes ``z``.  Either
    way the activation's derivative comes from the child scope
    ``recompute``."""
    n_rows, n_cols = shape
    with jax.named_scope("spmm.bwd"):
        dout = dout.astype(jnp.float32)
        dres = dout.astype(res_x.dtype) if ep.residual else None
        dz = dout
        from_out = (out is not None and ep.activation == "relu"
                    and not ep.residual and out.dtype == jnp.float32)
        if from_out:
            with jax.named_scope("recompute"):
                live = out > 0
            with jax.named_scope("act"):
                dz = jnp.where(live, dout, 0.0)
        elif ep.activation is not None:
            # recompute the pre-activation z through the oracle, then
            # pull dout back through the activation
            from ..core.schedule import ACTIVATIONS

            with jax.named_scope("recompute"):
                z = ref.spmm_coo_ref(rows, cols, vals, bb, n_rows)
                if ep.bias:
                    z = z + jnp.reshape(bias_x, (1, -1)).astype(jnp.float32)
            with jax.named_scope("act"):
                _, act_vjp = jax.vjp(ACTIVATIONS[ep.activation], z)
                dz, = act_vjp(dout)
        dbias = None
        if ep.bias:
            with jax.named_scope("dbias"):
                dbias = jnp.sum(dz, axis=0).astype(bias_x.dtype)
        dv = None
        if dvals:
            # dA values: sampled dense-dense product at the pattern
            with jax.named_scope("sddmm"):
                dv = ref.sddmm_ref(rows, cols, dz, bb).astype(vals.dtype)
        # dB: transpose SpMM (cols become the segment ids)
        with jax.named_scope("tspmm"):
            db = ref.spmm_coo_ref(cols, rows, vals, dz, n_cols).astype(bb.dtype)
        return dv, db, dbias, dres


def _spmm_quant_diff(qa: QuantizedCSR, b, sched: Schedule,
                     bias=None, residual=None):
    """Custom-VJP wrapper for the int8 quantized path: the scheduled
    kernel moves int8 codes + per-row scales forward; the backward runs
    the f32 ref path over the *dequantized* value stream.  Differentiable
    in ``b``/``bias``/``residual`` — the codes are host-calibrated data
    (see :func:`spmm`)."""
    ep = sched.epilogue
    coo = qa.csr.tocoo()  # cached on the inner CSR
    rows, cols = coo.rows, coo.cols
    vals_f = qa.dequantize().vals  # f32 stream for the ref backward

    def run(bb, bias_x, res_x):
        return kops.spmm(qa, bb, sched, bias=bias_x, residual=res_x)

    @jax.custom_vjp
    def _fn(bb, bias_x, res_x):
        return run(bb, bias_x, res_x)

    def _fwd(bb, bias_x, res_x):
        return run(bb, bias_x, res_x), (bb, bias_x, res_x)

    def _bwd(res, dout):
        bb, bias_x, res_x = res
        return _spmm_bwd(ep, rows, cols, qa.shape, vals_f, bb, bias_x,
                         res_x, dout, dvals=False)[1:]

    _fn.defvjp(_fwd, _bwd)
    return _fn(b, bias, residual)


def sddmm(rows, cols, a, b, scale=None, *, schedule=None,
          nnz_tile: int | None = None, impl: str = "pallas"):
    """vals[t] = <A[rows[t]], B[cols[t]]> (* scale[t]); rows/cols (nnz,).

    ``schedule`` supplies the nnz tile (its ``nnz_tile`` field); an
    explicit ``nnz_tile=`` overrides it.  ``schedule="tune"`` reuses the
    tuner's winner for this nnz profile (SDDMM only exposes the tile
    axis, so the tuned ``nnz_tile`` is what transfers).
    """
    if schedule is not None and nnz_tile is None:
        if isinstance(schedule, str) and schedule == "tune":
            from ..tune import tune_segment_reduce

            nnz_tile = tune_segment_reduce(
                rows, int(a.shape[1]),
                num_segments=int(jnp.max(rows)) + 1).schedule.nnz_tile
        else:
            nnz_tile = as_schedule(schedule).nnz_tile
    return kops.sddmm(rows, cols, a, b, scale,
                      nnz_tile=nnz_tile if nnz_tile else 256, impl=impl)


def segment_reduce(seg_ids, data, num_segments: int, schedule=None, *,
                   op: str = "sum"):
    """out[s] = ⨁_{t: seg_ids[t]=s} data[t] through the segment-group
    kernel, for ``op`` in 'sum' / 'max' / 'min' / 'mean'.

    'max'/'min' run the monoid-generalized strategy machinery (graph
    pooling — untouched segments come out as ±inf, matching
    ``jax.ops.segment_max``).  'mean' is realized as the add monoid with
    a count column fused into the same kernel pass (out = sums / counts;
    empty segments -> 0).  ``schedule`` carries (nnz_tile -> tile,
    group_size, strategy); ``schedule="tune"`` measures (tile, G,
    strategy) for this segment profile (cached by fingerprint); ragged
    inputs are identity-extended by the kernel wrapper."""
    if isinstance(schedule, str) and schedule == "tune":
        from ..tune import tune_segment_reduce

        sched = tune_segment_reduce(
            seg_ids, int(data.shape[1]), num_segments).schedule
    else:
        sched = as_schedule(schedule)
    if op == "mean":
        # one kernel pass: ride a ones column along the data, divide
        aug = jnp.concatenate(
            [data.astype(jnp.float32),
             jnp.ones((data.shape[0], 1), jnp.float32)], axis=1)
        out = _segment_reduce_kernel(
            seg_ids, aug, num_segments=num_segments, tile=sched.nnz_tile,
            group_size=sched.group_size, strategy=sched.strategy)
        return out[:, :-1] / jnp.maximum(out[:, -1:], 1.0)
    return _segment_reduce_kernel(
        seg_ids, data, num_segments=num_segments, tile=sched.nnz_tile,
        group_size=sched.group_size, strategy=sched.strategy,
        op="add" if op == "sum" else op)


# ---------------------------------------------------------------------------
# Fused sparse attention
# ---------------------------------------------------------------------------


def _attn_pattern(adj):
    """``(rows, cols, n_rows, bias)`` from an adjacency.

    A CSR adjacency contributes its *stored values* as an additive
    attention-score bias: ``s[t] = <Q[r_t], K[c_t]>·scale + vals[t]``
    (edge features / relative-position biases ride the adjacency).  The
    softmax is invariant to a per-row-constant shift, so the canonical
    all-ones "pattern" CSR attends identically to a pure pattern — but
    non-constant values now *matter* (they used to be silently ignored).
    An explicit ``(rows, cols, n_rows)`` tuple is a pure pattern
    (``bias=None``).
    """
    if isinstance(adj, CSR):
        coo = adj.tocoo()
        return coo.rows, coo.cols, adj.shape[0], coo.vals
    rows, cols, n_rows = adj
    return rows, cols, int(n_rows), None


def _attn_heads(q, k, v):
    """Normalize q/k/v to the kernel's node-major (n, H, ·) layout.
    2-D inputs are a single head; 3-D inputs are (n, H, ·) already.
    Returns (qh, kh, vh, multi).
    """
    if q.ndim == k.ndim == v.ndim == 2:
        return q[:, None], k[:, None], v[:, None], False
    if not (q.ndim == k.ndim == v.ndim == 3
            and q.shape[1] == k.shape[1] == v.shape[1]):
        raise ValueError(
            f"attention wants all-2-D (n, d) q/k/v or all-3-D (n, H, d) "
            f"with one shared head count H; got {q.shape}, {k.shape}, "
            f"{v.shape}")
    return q, k, v, True


def sparse_attention(adj, q, k, v, *, schedule=None,
                     scale: float | None = None, impl: str = "pallas",
                     score: str = "dot", slope: float = 0.2, keep=None):
    """One-pass sparse attention over a sparsity pattern:
    ``out[r] = Σ_t softmax_row(e_t) · keep_t · V[c_t]`` with
    ``e_t = <Q[r], K[c_t]> · scale + bias_t`` (``score='dot'``) or
    ``e_t = LeakyReLU_slope(Q[r] + K[c_t] + bias_t)`` (``score='additive'``,
    GAT's score: q and k are per-node, per-head terms of width 1).

    adj       a CSR adjacency — its pattern is attended over and its
              stored values are an additive score bias (row-constant
              values, e.g. the all-ones pattern CSR, cancel in the dot
              form's softmax, not under the additive form's LeakyReLU;
              see :func:`_attn_pattern`) — or a ``(rows, cols, n_rows)``
              pure-pattern tuple.
    q         (n_rows, d) queries, or (n_rows, H, d) for H heads;
    k         (n_cols, d) / (n_cols, H, d) keys;
    v         (n_cols, dv) / (n_cols, H, dv) values.  All H heads share
              the pattern and run in ONE kernel launch (heads lie in
              the kernel's lanes).
    keep      optional (nnz, H) (or (nnz,) for one head) mask on the
              normalised coefficients, in both directions: dropout's
              0 or 1/(1-p).
    schedule  supplies (nnz_tile, group_size, strategy) for the fused
              kernels; ``"tune"`` measures the real fused kernel for
              this pattern (``repro.tune.tune_sparse_attention``, cached
              by pattern fingerprint × head count × direction);
              'parallel' is excluded (its one-writeback contract does
              not hold for attention rows).
    impl      'pallas' (the fused kernels) or 'ref' (the spec oracle).

    Differentiable in q, k, v — the custom VJP runs the fused *backward*
    kernel, so ``impl="pallas"`` is fused in both directions.  The
    adjacency (pattern and value bias) and the keep mask are *data*, not
    differentiable operands.  ``schedule="tune"`` tunes the forward grid;
    the backward reuses that schedule.  Empty rows -> zero rows.
    """
    rows, cols, n_rows, bias = _attn_pattern(adj)
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    qh, kh, vh, multi = _attn_heads(q, k, v)
    if keep is not None and keep.ndim == 1:
        keep = keep[:, None]
    if impl == "ref":
        outs = [sparse_attention_ref(
                    rows, cols, qh[:, h], kh[:, h], vh[:, h], n_rows=n_rows,
                    scale=scale, bias=bias, score=score, slope=slope,
                    keep=None if keep is None else keep[:, h])
                for h in range(qh.shape[1])]
        out = jnp.stack(outs, axis=1)
        return out if multi else out[:, 0]
    if isinstance(schedule, str) and schedule == "tune":
        from ..tune import tune_sparse_attention

        sched = tune_sparse_attention(
            rows, cols, q, k, v, n_rows=n_rows, bias=bias,
            scale=scale).schedule
    else:
        sched = as_schedule(schedule)
    if sched.strategy == "parallel":
        raise ValueError(
            "sparse_attention cannot run the 'parallel' strategy: its "
            "single-writeback contract does not hold for attention rows")
    out = _sparse_attention_diff(rows, cols, qh, kh, vh, n_rows, scale,
                                 sched, bias, score=score, slope=slope,
                                 keep=keep)
    return out if multi else out[:, 0]


def _sparse_attention_diff(rows, cols, qh, kh, vh, n_rows, scale, sched,
                           bias=None, *, score="dot", slope=0.2, keep=None):
    """Custom-VJP core over node-major (n, H, ·) operands: fused Pallas
    forward (saving its output and the (m, l) softmax row stats — the
    O(H·n_rows) FlashAttention residuals), fused Pallas backward.  The
    additive score's forward takes its shift m from
    ``kernels.fused_attention.additive_shift`` and runs in one pass; the
    dot score's finds the row max in a pass of its own."""
    nnz = int(rows.shape[0])
    nnz_pad = max(round_up(max(nnz, 1), sched.nnz_tile), sched.nnz_tile)
    pad = lambda x: jnp.pad(x, ((0, nnz_pad - nnz),) + ((0, 0),) * (x.ndim - 1))  # noqa: E731
    lanes = dict(
        n_rows=n_rows, nnz=nnz, nnz_tile=sched.nnz_tile, scale=scale,
        group_size=sched.group_size, strategy=sched.strategy, score=score,
        slope=slope, bias=None if bias is None else pad(bias.astype(jnp.float32)),
        keep=None if keep is None else pad(keep.astype(jnp.float32)))
    rows_p, cols_p = pad(rows), pad(cols)

    def forward(q, k, v):
        shift = None
        if score == "additive":
            shift = additive_shift(rows, cols, q, k, bias, n_rows=n_rows,
                                   slope=slope)
        return _fused_attn_fwd(rows_p, cols_p, q, k, v, shift=shift, **lanes)

    @jax.custom_vjp
    def _fn(q, k, v):
        return forward(q, k, v)[0]

    def _fwd(q, k, v):
        out, m, l = forward(q, k, v)
        return out, (q, k, v, out, m, l)

    def _bwd(res, dout):
        q, k, v, out, m, l = res
        dq, dk, dv_ = _fused_attn_bwd(rows_p, cols_p, q, k, v, out, dout, m,
                                      l, **lanes)
        return (dq.astype(q.dtype), dk.astype(k.dtype),
                dv_.astype(v.dtype))

    _fn.defvjp(_fwd, _bwd)
    return _fn(qh, kh, vh)
