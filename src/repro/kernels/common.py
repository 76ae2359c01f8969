"""Shared in-kernel building blocks for the segment-group kernels.

``group_reduce_scatter`` is the Pallas dispatcher over the reduction-
strategy registry (``repro.core.schedule``): it looks up the strategy by
name and runs its in-kernel realization.  The built-in realizations live
here and are attached to the registry at import time; a user strategy
registered with only a pure-JAX spec falls back to running that spec on
the whole tile and combining the result (correct, not tuned).

Every realization is written against the strategy's reduction *monoid*
(``repro.core.Monoid``): the combine op, its identity, and the derived
reducers.  Sum is the ``add`` instance; ``op="max"``/``"min"`` run the
same machinery (graph pooling, the fused-attention row max) — a masked
lane becomes the monoid's identity, so no realization branches on it.

The built-in 'segment' realization is the TPU form of the paper's segment
group (DESIGN.md §2): within each width-G group it

1. finds segment runs by walking the group's row ids on the scalar unit
   (they sit in SMEM) — the GPU's runtime writeback-thread election,
2. reduces each run's lanes with one masked reduce over the group's
   (G, C) partials,
3. writes each run back with a read-modify-write into the output block
   — the analogue of the paper's multiple writeback threads; the
   sequential TPU grid makes the RMW race-free ("atomic" for free).

Strategy variants:
  'segment'     full machinery above (runtime writeback targets);
  'parallel'    contract: all lanes of a group share one segment -> plain
                within-group reduce + single writeback (one writeback
                thread);
  'accumulate'  per-lane RMW (the atomicAdd baseline).

Every Pallas launch of the package goes through :func:`pallas_call`, the
one place that decides compiled (TPU) vs interpreted (elsewhere,
:func:`pallas_interpret_default`), asks the compiler for the VMEM the
kernel's blocks need (:func:`vmem_bytes`) and names the launch after its
kernel.

``apply_epilogue`` is the shared last-grid-step epilogue applier
(``core.Epilogue``): bias / activation / residual / dtype cast fused
onto the output block (DESIGN.md §8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.schedule import (
    MONOIDS,
    Epilogue,
    Monoid,
    call_pallas_fn,
    attach_pallas_impl,
    get_strategy,
)

_ADD = MONOIDS["add"]

#: Finite masked-lane score floor shared by the attention kernels.  The
#: value is deliberately representable in float32 but NOT in float16
#: (fp16 max ~6.5e4): any kernel that compared or accumulated scores in
#: a low-precision input dtype would overflow it to -inf and poison the
#: softmax shift (exp(-inf - -inf) = NaN).  Kernels must
#: therefore run score arithmetic through :func:`upcast_f32` — the floor
#: doubles as a tripwire for precision regressions.
NEG_INF = -1e30


def upcast_f32(*xs):
    """Force float32 compute for (possibly fp16/bf16) kernel operands.

    Score accumulation, softmax statistics and the probability
    algebra must happen in f32 regardless of the storage dtype: besides
    the :data:`NEG_INF` floor overflowing fp16, bf16's 8-bit mantissa
    loses the `exp(s - m)` cancellation.  Returns one array for one
    argument, a tuple otherwise.
    """
    out = tuple(x.astype(jnp.float32) for x in xs)
    return out[0] if len(out) == 1 else out


#: VMEM of one v5e TensorCore: 128 MiB physical.  A kernel that asks
#: for nothing gets the compiler's 16 MiB scoped default; ``pallas_call``
#: below asks for what the kernel's blocks need.
VMEM_CAPACITY = 128 * 1024 * 1024
#: VMEM the compiler keeps for its own scratch (semaphores, relayout
#: buffers, spills) on top of the blocks a kernel declares.
VMEM_HEADROOM = 4 * 1024 * 1024


def vmem_bytes(shape, dtype, buffers: int = 1) -> int:
    """VMEM one block occupies: Mosaic tiles the last two dims by
    (sublanes, 128 lanes), with 8 sublanes of 32-bit words and packing
    narrower dtypes (16 rows of bf16, 32 of int8/fp8) — so a (K, 16) f32
    block takes as much VMEM as a (K, 128) one.  A one-row block, such
    as a ``(1, T)`` lane block, is tiled one row high.  ``buffers``
    counts the pipeline's copies (:func:`block_buffers`)."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, r, c = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = 1 if r == 1 else 8 * max(1, 4 // itemsize)
    n = 1
    for d in lead:
        n *= d
    return buffers * n * (-(-r // sub) * sub) * (-(-c // 128) * 128) * itemsize


def block_buffers(n_blocks: int) -> int:
    """Pipeline copies Mosaic allocates for a block that takes
    ``n_blocks`` distinct positions over the grid: one for a block that
    never moves (resident for the whole launch), two (double-buffered)
    otherwise."""
    return 1 if n_blocks == 1 else 2


def pallas_interpret_default() -> bool:
    """Whether Pallas kernels run interpreted on this backend: True
    off-TPU (interpret mode is the only Pallas path on CPU), False on TPU
    hardware, where every kernel runs compiled."""
    return jax.default_backend() != "tpu"


def pallas_call(kernel, *, name: str, vmem_need: int | None = None,
                interpret=None, **kw):
    """``pl.pallas_call`` with the one compiled-vs-interpreted decision
    of the kernels package: ``interpret=None`` follows the backend
    (:func:`pallas_interpret_default`, looked up at every call: compiled
    on a TPU, interpreted elsewhere).  Asking for the interpreter on a
    TPU raises — a kernel that cannot lower fails there, it never falls
    back.

    ``name`` is the kernel's stable name, the one its launches carry in
    the compiled program and in a profiler trace; every launch has one.

    Compiled with ``vmem_need`` given (the padded, buffered bytes of the
    kernel's blocks and scratch — see :func:`vmem_bytes`), the kernel's
    scoped VMEM limit is that plus :data:`VMEM_HEADROOM`, capped at the
    chip's :data:`VMEM_CAPACITY`; a kernel whose blocks exceed the cap
    is refused by the compiler.  Without it the compiler's default
    scoped limit holds."""
    default = pallas_interpret_default()
    if interpret is None:
        interpret = default
    elif interpret and not default:
        raise ValueError("Pallas kernels run compiled on a TPU; the "
                         "interpreter is for backends without one")
    if not interpret and vmem_need is not None:
        from jax.experimental.pallas import tpu as pltpu

        kw["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=min(vmem_need + VMEM_HEADROOM, VMEM_CAPACITY))
    return pl.pallas_call(kernel, name=name, interpret=interpret, **kw)


def _rmw_row(out_ref, row, delta, combine):
    """out_ref[row, :] = combine(out_ref[row, :], delta); delta (1, C),
    dynamic row index."""
    idx = (pl.dslice(row, 1), slice(None))
    out_ref[idx] = combine(out_ref[idx], delta).astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# Built-in in-kernel realizations.  They read their operands through refs:
#     pallas_fn(rows_ref (1, T) SMEM, partial_ref (T, C) VMEM,
#               out_ref (R, C), group_size, monoid=<Monoid>)
# Writeback rows are scalars, and Mosaic reads scalars only from SMEM;
# per-lane and per-run slices of the partials are dynamic row windows of
# a VMEM ref.  A user realization keeps the value contract
# ``(rows (T,), partial (T, C), out_ref, group_size)`` (see
# ``group_reduce_scatter``).
# ---------------------------------------------------------------------------


def _pallas_accumulate(rows_ref, partial_ref, out_ref, group_size: int, *,
                       monoid: Monoid = _ADD):
    del group_size

    def lane_body(t, c):
        _rmw_row(out_ref, rows_ref[0, t], partial_ref[pl.ds(t, 1), :],
                 monoid.combine)
        return c

    jax.lax.fori_loop(0, partial_ref.shape[0], lane_body, 0)


def _pallas_parallel(rows_ref, partial_ref, out_ref, group_size: int, *,
                     monoid: Monoid = _ADD):
    G = group_size

    def group_body(n, c):
        base = pl.multiple_of(n * G, G)
        p = partial_ref[pl.ds(base, G), :]
        _rmw_row(out_ref, rows_ref[0, base], monoid.reduce(p, 0)[None, :],
                 monoid.combine)
        return c

    jax.lax.fori_loop(0, partial_ref.shape[0] // G, group_body, 0)


def _pallas_segment(rows_ref, partial_ref, out_ref, group_size: int, *,
                    monoid: Monoid = _ADD):
    G = group_size
    lane = jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)

    def group_body(n, c):
        base = pl.multiple_of(n * G, G)
        p = partial_ref[pl.ds(base, G), :]

        def flush(lo, hi):
            # one run = lanes [lo, hi) of the group: one masked reduce,
            # one writeback (the paper's writeback thread of that run)
            run = jnp.where((lane >= lo) & (lane < hi), p, monoid.identity)
            _rmw_row(out_ref, rows_ref[0, base + lo],
                     monoid.reduce(run, 0)[None, :], monoid.combine)

        def lane_body(t, start):
            # a row change closes the open run (runtime writeback
            # election, on the scalar unit)
            brk = rows_ref[0, base + t] != rows_ref[0, base + t - 1]

            @pl.when(brk)
            def _():
                flush(start, t)

            return jnp.where(brk, t, start)

        flush(jax.lax.fori_loop(1, G, lane_body, jnp.int32(0)), G)
        return c

    jax.lax.fori_loop(0, partial_ref.shape[0] // G, group_body, 0)


_REF_REALIZATIONS = frozenset(
    (_pallas_accumulate, _pallas_parallel, _pallas_segment))

#: output rows a window covers beyond its chunk's lanes: one sublane
#: tile, so a window aligned down to it still spans the chunk
WINDOW_SLACK = 8
#: widest chunk of a tile one window product takes: at 128 lanes its
#: 0/1 matrix and its result stay in vector registers (a 256-lane
#: product spills them to VMEM)
WINDOW_LANES = 128


def segment_window(strategy, n_rows: int, nnz_tile: int):
    """``(lanes, rows)`` of the MXU window products an nnz tile's
    'segment' add-reduce runs as (:func:`window_reduce_scatter`): one a
    chunk of ``lanes`` lanes, into ``rows`` output rows.  None where
    every tile walks its runs: a strategy other than the built-in
    'segment' realization, or an output block shorter than a window."""
    lanes = WINDOW_LANES if nnz_tile % WINDOW_LANES == 0 else nnz_tile
    rows = lanes + WINDOW_SLACK
    seg = get_strategy(strategy).pallas_fn is _pallas_segment
    return (lanes, rows) if seg and n_rows >= rows else None


def window_start(lo, hi, n_rows: int, window: int, xp=jnp):
    """``(start, fits)`` of the window of a chunk whose lanes' rows run
    from ``lo`` (the lowest) to ``hi`` (the highest), in any lane order:
    ``lo`` aligned down to a sublane tile, clamped so the ``window`` rows
    stay inside the ``n_rows`` block; the chunk fits when ``hi`` lies
    inside.  Scalars in the kernel; ``xp=numpy`` over every chunk at once
    on the host, where the program counts the tiles that fit."""
    start = xp.minimum(lo & -WINDOW_SLACK, n_rows - window)
    return start, hi - start < window


def window_reduce_scatter(rows_ref, lanes_ref, partial_ref, out_ref,
                          group_size: int, strategy: str):
    """The 'segment' add-reduce of a tile as MXU products: for each
    chunk of the tile's lanes (``segment_window``), a (W, L) 0/1 matrix,
    ``hit[w, t] = rows[t] == start + w``, times the chunk's partials
    (L, C), added into output rows ``[start, start + W)``.  ``lanes_ref``
    holds the tile's row ids as a (1, T) VMEM lane block, ``rows_ref``
    the same in SMEM.  Each chunk's window comes from the lowest and
    highest of its rows (:func:`window_start`), so the lanes may come in
    any order.  The products run at float32 (``HIGHEST``) on exact 0/1
    weights, so they sum what the run walk sums.  A tile with a chunk
    whose rows span more than its window (many empty rows inside it)
    takes the walk, ``group_reduce_scatter(..., strategy)``,
    unchanged."""
    T = partial_ref.shape[0]
    n_rows = out_ref.shape[0]
    lanes, w = segment_window(strategy, n_rows, T)

    def window(lo):
        ids = lanes_ref[:, lo:lo + lanes]
        return window_start(jnp.min(ids), jnp.max(ids), n_rows, w)

    wins = [window(lo) for lo in range(0, T, lanes)]
    fits = functools.reduce(jnp.logical_and, [ok for _, ok in wins])

    @pl.when(fits)
    def _window():
        # one chunk an iteration: unrolled, the chunks' products would
        # be live at once and spill their registers to VMEM
        def body(c, carry):
            lo = pl.multiple_of(c * lanes, lanes)
            start = wins[0][0]
            for k, (other, _) in enumerate(wins[1:], 1):
                start = jnp.where(c == k, other, start)
            hit = (jax.lax.broadcasted_iota(jnp.int32, (w, lanes), 0)
                   + start == lanes_ref[:, pl.ds(lo, lanes)])
            delta = jnp.dot(hit.astype(jnp.float32),
                            partial_ref[pl.ds(lo, lanes), :],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
            _add_window(out_ref, start, w, delta)
            return carry

        jax.lax.fori_loop(0, T // lanes, body, 0)

    @pl.when(jnp.logical_not(fits))
    def _walk():
        group_reduce_scatter(rows_ref, partial_ref, out_ref, group_size,
                             strategy)


def _add_window(out_ref, start, w: int, delta):
    """out_ref[start:start + w] += delta: at a dynamic offset where
    ``start`` is sublane-aligned, else at the static last ``w`` rows
    (only a window clamped there starts unaligned)."""
    def add(rows):
        out_ref[rows, :] = (out_ref[rows, :] + delta).astype(out_ref.dtype)

    aligned = (start & (WINDOW_SLACK - 1)) == 0

    @pl.when(aligned)
    def _inside():
        add(pl.ds(pl.multiple_of(start, WINDOW_SLACK), w))

    @pl.when(jnp.logical_not(aligned))
    def _clamped():
        add(pl.ds(out_ref.shape[0] - w, w))


def spec_fallback_pallas(entry):
    """Bridge a pure-JAX strategy spec into the in-kernel contract: run the
    spec over the whole tile (num_segments = the output block height) and
    combine into the block.  Correct for any spec; no per-group tuning."""
    from ..core.schedule import call_spec_fn

    def pallas_fn(rows, partial, out_ref, group_size: int, *,
                  monoid: Monoid = _ADD):
        tile = call_spec_fn(entry, partial, rows, out_ref.shape[0],
                            group_size)
        out_ref[...] = monoid.combine(out_ref[...], tile).astype(
            out_ref.dtype)

    return pallas_fn


def group_reduce_scatter(rows_ref, partial_ref, out_ref, group_size: int,
                         strategy: str = "segment", op=None):
    """Reduce the partials ``partial_ref`` (T, C) by the row indices
    ``rows_ref`` (1, T, in SMEM) into ``out_ref`` (R, C) with the
    registered strategy named ``strategy`` under the reduction monoid
    ``op`` names ('add' default / 'max' / 'min' / a Monoid).

    ``rows`` need not be sorted; sorted input minimizes writebacks
    (each unsorted transition opens a new run — correct, just more RMWs),
    which is exactly the paper's "writeback thread decided at runtime".
    The built-in realizations read the refs; a user realization (or the
    spec bridge) gets the loaded ``(rows (T,), partial (T, C))`` values,
    which only the interpreter can load from SMEM as a vector.
    """
    T = partial_ref.shape[0]
    assert T % group_size == 0, (T, group_size)
    entry = get_strategy(strategy, op=op)
    if entry.pallas_fn in _REF_REALIZATIONS:
        call_pallas_fn(entry.pallas_fn, rows_ref, partial_ref, out_ref,
                       group_size, entry.monoid)
        return
    fn = entry.pallas_fn or spec_fallback_pallas(entry)
    call_pallas_fn(fn, rows_ref[0, :], partial_ref[...], out_ref,
                   group_size, entry.monoid)


def split_epilogue_refs(refs, epilogue: Epilogue, narrowed: bool):
    """Unpack a kernel's trailing refs under the shared epilogue operand
    layout ``[bias?][residual?] out [f32 acc scratch if narrowed]`` —
    one place encodes the positional contract for every epilogued
    kernel.  Returns ``(bias_ref, res_ref, out_ref, acc_ref)`` with
    ``acc_ref is None`` when the output block doubles as the
    accumulator."""
    acc_ref = refs[-1] if narrowed else None
    extras = list(refs[:-2] if narrowed else refs[:-1])
    out_ref = refs[-2] if narrowed else refs[-1]
    bias_ref = extras.pop(0) if epilogue.bias else None
    res_ref = extras.pop(0) if epilogue.residual else None
    return bias_ref, res_ref, out_ref, acc_ref


def apply_epilogue(out_ref, epilogue: Epilogue, bias_ref=None,
                   res_ref=None, acc_ref=None):
    """Apply an :class:`~repro.core.Epilogue` to a kernel's output block
    in place — called on the *last* reduction grid step (under
    ``pl.when``), when the accumulator holds the fully-reduced f32
    result.  ``acc_ref`` is the f32 scratch accumulator kernels use when
    ``out_dtype`` narrows the output (accumulation must stay f32; only
    the final store casts); without it the output block doubles as the
    accumulator."""
    src = out_ref if acc_ref is None else acc_ref
    acc = src[...].astype(jnp.float32)
    acc = epilogue.apply(
        acc,
        bias=None if bias_ref is None else bias_ref[...],
        residual=None if res_ref is None else res_ref[...],
    )
    out_ref[...] = acc.astype(out_ref.dtype)


attach_pallas_impl("accumulate", _pallas_accumulate)
attach_pallas_impl("parallel", _pallas_parallel)
attach_pallas_impl("segment", _pallas_segment)
