"""jit'd wrappers around the Pallas kernels: format glue, padding (zero
extension), and result cropping. These are what the rest of the framework
calls; the raw kernels stay shape-strict.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

import jax

from ..core.dtypes import operand_dtype, storage_dtype
from ..core.schedule import ACTIVATIONS, Epilogue, Schedule
from ..sparse.formats import (
    CSR,
    ELL,
    BlockedCOO,
    GroupedCOO,
    QuantizedCSR,
    _memoized,
    round_up,
)
from . import ref
from .common import VMEM_CAPACITY, VMEM_HEADROOM, segment_window, window_start
from .grouped_matmul import grouped_matmul as _gmm_pallas
from .sddmm import sddmm as _sddmm_kernel
from .spmm_eb import eb_windows, vmem_need_eb
from .spmm_eb import spmm_eb as _spmm_eb
from .spmm_eb import spmm_eb_stream as _spmm_eb_stream
from .spmm_rb import rb_width_tile, vmem_need_rb
from .spmm_rb import spmm_rb as _spmm_rb

_NOOP_EP = Epilogue()

#: VMEM a schedule's blocks may claim: the chip's VMEM less the
#: compiler's headroom (``common.pallas_call`` asks for exactly the
#: footprint, so this is the feasibility bound, not the 16 MiB default
#: scoped limit a kernel gets without asking).
_VMEM_BUDGET = VMEM_CAPACITY - VMEM_HEADROOM


def _pad_cols(b, col_tile):
    k, n = b.shape
    n_pad = round_up(n, col_tile)
    if n_pad != n:
        b = jnp.pad(b, ((0, 0), (0, n_pad - n)))
    return b, n


def _col_tile(sched: Schedule, n: int) -> int:
    """The column tile ``spmm`` launches with for an N-wide dense operand:
    the schedule's tile rounded up to whole 128-lane vregs, shrunk to the
    8-padded width.  Mosaic takes a block's last dim whole or in
    multiples of 128, and a narrower tile fills the same lane-padded
    VMEM, so a tile under 128 would only add grid steps."""
    return min(round_up(sched.col_tile, 128), round_up(n, 8))


def _eb_need(sched: Schedule, col_tile: int, n=None) -> dict:
    """``spmm_eb.vmem_need_eb``'s keywords for a schedule's launch."""
    vd = sched.value_dtype
    return dict(nnz_tile=sched.nnz_tile, col_tile=col_tile, n=n,
                b_dtype=operand_dtype(vd), vals_dtype=storage_dtype(vd),
                epilogue=sched.epilogue, strategy=sched.strategy)


def vmem_footprint_eb(k, n_rows, sched: Schedule, windows=None) -> int:
    """VMEM the EB kernel claims for this schedule when the dense operand
    spans more than one column tile: padded (sublane, 128) tiles and
    pipeline buffers of the schedule's value storage and dense operand,
    as ``spmm_eb.vmem_need_eb`` counts them (``windows``: of the
    windowed launch)."""
    return vmem_need_eb(k, n_rows, windows=windows,
                        **_eb_need(sched, sched.col_tile))


def eb_stream_windows(shape, n: int, sched: Schedule):
    """``(row_window, col_window)`` of the windowed eb launch ``spmm``
    runs for an ``shape`` matrix times an ``n``-wide dense operand under
    ``sched``, or None where the resident launch fits the VMEM budget
    (``spmm_eb.eb_windows``): decided from the operand and output bytes
    alone."""
    col_tile = _col_tile(sched, n)
    return eb_windows(shape[1], shape[0], budget=_VMEM_BUDGET,
                      **_eb_need(sched, col_tile, round_up(n, col_tile)))


def vmem_footprint_rb(k, width, sched: Schedule) -> int:
    """VMEM the RB kernel claims for this schedule (see
    ``spmm_rb.vmem_need_rb``): the whole-K B block plus the ELL value
    slab, the gathered-slot scratch, and the output block."""
    vd = sched.value_dtype
    return vmem_need_rb(k, row_tile=sched.row_tile, col_tile=sched.col_tile,
                        width_tile=rb_width_tile(max(width, 1)),
                        b_dtype=operand_dtype(vd),
                        vals_dtype=storage_dtype(vd), epilogue=sched.epilogue)


def eb_stream_layout(a: CSR, n: int, sched: Schedule):
    """``(BlockedCOO, col_tile)`` of the windowed eb launch that
    ``spmm(a, b, sched)`` runs for an ``n``-wide ``b`` (the memoized
    partition itself), or None where the launch runs resident."""
    windows = eb_stream_windows(a.shape, n, sched)
    if windows is None:
        return None
    return a.blocked(sched.nnz_tile, windows), _col_tile(sched, n)


def schedule_fits_vmem(sched: Schedule, *, n_rows: int, n_cols: int,
                       row_max: int = 0, budget: int = _VMEM_BUDGET) -> bool:
    """Whether a schedule's per-cell working set fits the VMEM budget —
    the feasibility predicate the autotuner prunes candidates with before
    spending measurement time on them.  An eb schedule is counted in the
    layout its launch runs: resident, or windowed where that does not
    fit."""
    if sched.kernel == "eb":
        # the windowed launch stands in where the resident one does not fit
        try:
            windows = eb_windows(n_cols, n_rows, budget=budget,
                                 **_eb_need(sched, sched.col_tile))
        except ValueError:
            return False
        need = vmem_footprint_eb(n_cols, n_rows, sched, windows)
    else:
        need = vmem_footprint_rb(n_cols, max(row_max, 1), sched)
    return need <= budget


def _pad_epilogue_operands(ep, bias, residual, n_rows, n_pad):
    """Pad the epilogue's array operands to the kernel layout: bias
    (1, n_pad), residual (n_rows, n_pad).  Presence was validated by
    ``spmm`` before the impl branch (ref and pallas fail identically)."""
    bias_p = res_p = None
    if ep.bias:
        bias_p = jnp.reshape(bias, (1, -1))
        bias_p = jnp.pad(bias_p, ((0, 0), (0, n_pad - bias_p.shape[1])))
    if ep.residual:
        res_p = jnp.pad(residual, ((0, n_rows - residual.shape[0]),
                                   (0, n_pad - residual.shape[1])))
    return bias_p, res_p


def eb_window_tiles(a: GroupedCOO, strategy) -> np.ndarray:
    """(num_tiles,) bool: the nnz tiles of an eb launch over ``a``'s
    (concrete) lanes whose 'segment' reduce runs as MXU window products,
    by the predicate the kernel evaluates on each chunk of a tile
    (``common.window_start`` of the chunk's lowest and highest rows); the
    leading heavy tiles of a skew layout run 'parallel'."""
    fits = np.zeros(a.num_tiles, bool)
    win = segment_window(strategy, a.shape[0], a.nnz_tile)
    if win is not None:
        lanes, w = win
        rows = np.asarray(a.rows).reshape(a.num_tiles, -1, lanes)
        _, ok = window_start(rows.min(axis=-1), rows.max(axis=-1),
                             a.shape[0], w, xp=np)
        fits[a.heavy_tiles:] = ok[a.heavy_tiles:].all(axis=1)
    return fits


def _cast_stream(fmt, vals, dt):
    """Memoized cast of a format's value stream to storage dtype ``dt``
    (keyed on the format instance, so a serving loop casts once)."""
    if vals.dtype == dt:
        return vals
    return _memoized(fmt, (vals,), ("vals_astype", str(jnp.dtype(dt))),
                     lambda: vals.astype(dt))


def spmm(a, b, schedule: Schedule | None = None, *,
         bias=None, residual=None, impl: str = "pallas"):
    """out = A @ B for sparse A (CSR / QuantizedCSR / GroupedCOO /
    BlockedCOO / ELL) and dense B, with the schedule's fused epilogue
    applied in-kernel.

    impl='ref' runs the pure-jnp oracle (epilogue applied via its
    executable spec); impl='pallas' runs the kernel the schedule selects
    (eb -> GroupedCOO path, rb -> ELL path).  CSR inputs convert through
    the per-(format, tile) cache on CSR.  Where the eb launch's blocks
    exceed the VMEM budget (:func:`eb_stream_windows`), the lanes run as
    a BlockedCOO through the windowed launch, ``spmm_eb_stream``.
    ``bias`` (N,) / ``residual`` (n_rows, N) are required exactly when
    ``schedule.epilogue`` declares them.

    ``schedule.value_dtype`` (DESIGN.md §13) selects the storage width
    the kernel *moves*: narrow floats cast the value stream and B to
    that dtype (memoized per instance); 'int8' routes through the
    quantized path — a CSR is quantized once (per-row scales, memoized),
    a :class:`QuantizedCSR` feeds its codes directly, and B narrows to
    bf16.  Accumulation stays f32 either way (``upcast_f32``).
    """
    if schedule is None:
        schedule = Schedule("eb")
    ep = schedule.epilogue
    if ep.bias and bias is None:
        raise ValueError("schedule epilogue declares bias=True but no "
                         "bias array was passed")
    if ep.residual and residual is None:
        raise ValueError("schedule epilogue declares residual=True but "
                         "no residual array was passed")

    if impl == "ref":
        if isinstance(a, QuantizedCSR):
            a = a.dequantize()
        if isinstance(a, GroupedCOO):
            out = ref.spmm_coo_ref(a.rows, a.cols, a.vals, b, a.shape[0])
        elif isinstance(a, BlockedCOO):
            rows, cols = a.global_lanes()
            out = ref.spmm_coo_ref(rows, cols, a.vals, b, a.shape[0])
        elif isinstance(a, CSR):
            coo = a.tocoo()
            out = ref.spmm_coo_ref(coo.rows, coo.cols, coo.vals, b,
                                   a.shape[0])
        elif isinstance(a, ELL):
            out = ref.spmm_ell_ref(a.cols, a.vals, b, a.shape[0])
        else:
            raise TypeError(type(a))
        if ep.is_noop:
            return out
        return ep.apply(out, bias=bias, residual=residual)

    vd = schedule.value_dtype
    scales = None
    if isinstance(a, QuantizedCSR) or vd == "int8":
        if isinstance(a, CSR):
            a = a.quantized()  # memoized host-side calibration pass
        if not isinstance(a, QuantizedCSR):
            raise TypeError(
                "value_dtype='int8' needs a CSR or QuantizedCSR input "
                "(the per-row scales are a CSR-level calibration); got "
                f"{type(a).__name__}")
        scales = a.scales
        a = a.csr  # int8 codes on the original pattern
        b = b.astype(operand_dtype("int8"))
    elif vd is not None:
        b = b.astype(operand_dtype(vd))

    col_tile = _col_tile(schedule, b.shape[1])
    b_pad, n = _pad_cols(b, col_tile)
    n_pad = b_pad.shape[1]

    if schedule.kernel == "eb":
        windows = eb_stream_windows(a.shape, n, schedule)
        if windows is not None and scales is not None:
            raise NotImplementedError(
                "int8 codes run the resident eb launch only; these operands "
                "exceed VMEM")
        skew_kw = dict(group_size=schedule.group_size,
                       split_threshold=schedule.split_threshold,
                       merge_threshold=schedule.merge_threshold)
        if windows is not None and isinstance(a, CSR):
            a = a.blocked(schedule.nnz_tile, windows)
        if isinstance(a, CSR):
            a = a.grouped(schedule.nnz_tile, **skew_kw)
        if windows is not None and isinstance(a, GroupedCOO):
            a = a.regrouped(schedule.nnz_tile).blocked(windows)
        if isinstance(a, BlockedCOO):
            if a.nnz_tile != schedule.nnz_tile:
                raise ValueError(f"a BlockedCOO of {a.nnz_tile}-lane tiles "
                                 f"under a {schedule.nnz_tile}-lane schedule")
            vals = (a.vals if vd is None
                    else _cast_stream(a, a.vals, storage_dtype(vd)))
            bias_p, res_p = _pad_epilogue_operands(ep, bias, residual,
                                                   a.shape[0], n_pad)
            out = _spmm_eb_stream(
                a.rows, a.cols, vals, a.tile_rows, a.tile_cols, b_pad,
                n_rows=a.shape[0], row_window=a.windows[0],
                col_window=a.windows[1], nnz_tile=a.nnz_tile,
                col_tile=col_tile, group_size=schedule.group_size,
                strategy=schedule.strategy, epilogue=ep, bias=bias_p,
                residual=res_p)
            return out[:, :n]
        assert isinstance(a, GroupedCOO), type(a)
        a = a.regrouped(schedule.nnz_tile, **skew_kw)  # memoized; no-op
        vals = (a.vals if vd is None or scales is not None
                else _cast_stream(a, a.vals, storage_dtype(vd)))
        bias_p, res_p = _pad_epilogue_operands(ep, bias, residual,
                                               a.shape[0], n_pad)
        out = _spmm_eb(
            a.rows, a.cols, vals, b_pad, n_rows=a.shape[0],
            nnz_tile=schedule.nnz_tile, col_tile=col_tile,
            group_size=schedule.group_size, strategy=schedule.strategy,
            heavy_tiles=a.heavy_tiles, epilogue=ep, scales=scales,
            bias=bias_p, residual=res_p)
        return out[:, :n]

    # rb path
    if isinstance(a, CSR):
        a = a.ell(row_tile=schedule.row_tile)
    assert isinstance(a, ELL), type(a)
    r_pad = round_up(a.n_rows_padded, schedule.row_tile)
    ecols, evals = a.cols, a.vals
    if vd is not None and scales is None:
        evals = _cast_stream(a, evals, storage_dtype(vd))
    if r_pad != a.n_rows_padded:
        pad = r_pad - a.n_rows_padded
        ecols = jnp.pad(ecols, ((0, pad), (0, 0)))
        evals = jnp.pad(evals, ((0, pad), (0, 0)))
    scales_p = None
    if scales is not None:
        # per-row scales aligned to the padded row axis; padded rows
        # carry val 0, so the filler scale value is never observable
        scales_p = jnp.pad(scales, (0, r_pad - scales.shape[0]),
                           constant_values=1.0)
    bias_p, res_p = _pad_epilogue_operands(ep, bias, residual, r_pad, n_pad)
    out = _spmm_rb(ecols, evals, b_pad, row_tile=schedule.row_tile,
                   col_tile=col_tile, epilogue=ep, scales=scales_p,
                   bias=bias_p, residual=res_p)
    return out[: a.shape[0], :n]


def sddmm(rows, cols, a, b, scale=None, *, nnz_tile: int = 256,
          impl: str = "pallas"):
    """vals[t] = <A[rows[t]], B[cols[t]]> (* scale[t]); rows/cols (nnz,).

    ``scale=None`` skips the scale operand entirely (no ``ones((nnz,))``
    materialized per call): padded lanes are legal by the zero-extension
    rule — padding is strictly trailing and cropped by ``out[:nnz]``.
    """
    if impl == "ref":
        return ref.sddmm_ref(rows, cols, a, b, scale)
    nnz = rows.shape[0]
    nnz_pad = round_up(max(nnz, 1), nnz_tile)
    pad = nnz_pad - nnz
    rows_p = jnp.pad(rows, (0, pad))
    cols_p = jnp.pad(cols, (0, pad))
    # zero scale masks padded lanes (None: trailing garbage is cropped)
    scale_p = None if scale is None else jnp.pad(scale, (0, pad))
    d = a.shape[1]
    d_tile = min(128, round_up(d, 8))
    d_pad = round_up(d, d_tile)
    if d_pad != d:
        a = jnp.pad(a, ((0, 0), (0, d_pad - d)))
        b = jnp.pad(b, ((0, 0), (0, d_pad - d)))
    out = _sddmm_kernel(rows_p, cols_p, a, b, scale_p, nnz_tile=nnz_tile,
                        d_tile=d_tile)
    return out[:nnz]


def expert_tile_map(group_sizes: np.ndarray, token_tile: int) -> np.ndarray:
    """tile -> expert map for capacity-padded grouped matmul: expert e owns
    ceil(group_sizes[e] / token_tile) consecutive tiles."""
    tiles = []
    for e, g in enumerate(group_sizes):
        tiles.extend([e] * int(np.ceil(g / token_tile)))
    return np.asarray(tiles, np.int32)


def grouped_matmul_ref(x, tile_experts, weights, *, bias=None,
                       epilogue: Epilogue = _NOOP_EP,
                       token_tile: int = 128):
    """Pure-jnp oracle for the epilogued grouped matmul: per token tile i
    with expert e = tile_experts[i],
    ``y = epilogue(x_tile @ weights[e], bias=bias[e])``."""
    t_pad, d = x.shape
    xt = x.reshape(-1, token_tile, d).astype(jnp.float32)
    wt = weights[tile_experts].astype(jnp.float32)  # (NT, D, F)
    z = jnp.einsum("ntd,ndf->ntf", xt, wt)
    b = (None if bias is None
         else bias[tile_experts][:, None, :].astype(jnp.float32))
    y = epilogue.apply(z, bias=b)
    return y.reshape(t_pad, -1)


def grouped_matmul(x, tile_experts, weights, *, bias=None,
                   epilogue: Epilogue = _NOOP_EP, token_tile: int = 128,
                   f_tile: int = 128, d_tile: int = 128,
                   impl: str = "pallas"):
    """Differentiable epilogued grouped matmul — the MoE expert GEMM as
    one Pallas launch per tile (GEMM + bias/activation/cast fused onto
    the output block; ``repro.fuse`` routes ``grouped_matmul`` chain
    nodes here).

    x (T_pad, D) expert-sorted tokens, tile_experts (T_pad//token_tile,)
    int32, weights (E, D, F), bias (E, F) iff ``epilogue.bias``.
    Differentiable in x, weights and bias: Pallas forward, pure-JAX ref
    backward (recompute z, activation VJP, segment scatter-add into the
    expert axis).  ``tile_experts`` is routing data, not an operand.
    """
    assert epilogue.bias == (bias is not None)
    if impl == "ref":
        return grouped_matmul_ref(x, tile_experts, weights, bias=bias,
                                  epilogue=epilogue, token_tile=token_tile)

    def run(xx, ww, bb):
        return _gmm_pallas(xx, tile_experts, ww, bias=bb,
                           epilogue=epilogue, token_tile=token_tile,
                           f_tile=f_tile, d_tile=d_tile)

    @jax.custom_vjp
    def fn(xx, ww, bb):
        return run(xx, ww, bb)

    def fwd(xx, ww, bb):
        return run(xx, ww, bb), (xx, ww, bb)

    def bwd(res, dout):
        xx, ww, bb = res
        t_pad, d = xx.shape
        f = ww.shape[2]
        xt = xx.reshape(-1, token_tile, d).astype(jnp.float32)
        wt = ww[tile_experts].astype(jnp.float32)  # (NT, D, F)
        dz = dout.astype(jnp.float32).reshape(-1, token_tile, f)
        if epilogue.activation is not None:
            z = jnp.einsum("ntd,ndf->ntf", xt, wt)
            if epilogue.bias:
                z = z + bb[tile_experts][:, None, :].astype(jnp.float32)
            _, act_vjp = jax.vjp(ACTIVATIONS[epilogue.activation], z)
            dz, = act_vjp(dz)
        dx = jnp.einsum("ntf,ndf->ntd", dz, wt).reshape(t_pad, d).astype(
            xx.dtype)
        dw = jnp.zeros(ww.shape, jnp.float32).at[tile_experts].add(
            jnp.einsum("ntd,ntf->ndf", xt, dz)).astype(ww.dtype)
        db = None
        if epilogue.bias:
            db = jnp.zeros(bb.shape, jnp.float32).at[tile_experts].add(
                jnp.sum(dz, axis=1)).astype(bb.dtype)
        return dx, dw, db

    fn.defvjp(fwd, bwd)
    return fn(x, weights, bias)
