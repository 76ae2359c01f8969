"""Per-kernel allclose sweeps against the pure-jnp oracles (ref.py).

Sweeps shapes, dtypes, schedules (nnz_tile/row_tile/col_tile/group_size)
and strategies, per the paper's tuning axes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Epilogue,
    GroupReduceStrategy,
    KernelSchedule,
    segment_group_reduce,
)
from repro.kernels import grouped_matmul, ref, sddmm, segment_reduce, spmm
from repro.kernels.ops import expert_tile_map
from repro.sparse import CSR, random_csr

RTOL = 2e-5
ATOL = 2e-5


def _want_spmm(csr, b):
    return np.asarray(spmm(csr, b, impl="ref"))


@pytest.mark.parametrize("density,skew", [(0.02, 0.0), (0.05, 1.5), (0.001, 0.0)])
@pytest.mark.parametrize(
    "sched",
    [
        KernelSchedule("eb", nnz_tile=64, col_tile=8, group_size=8),
        KernelSchedule("eb", nnz_tile=64, col_tile=16, group_size=64),
        KernelSchedule("eb", nnz_tile=128, col_tile=8, group_size=16),
        KernelSchedule("eb", nnz_tile=64, col_tile=8, group_size=32,
                       strategy="accumulate"),
    ],
)
def test_spmm_eb_schedule_sweep(density, skew, sched):
    csr = random_csr(200, 150, density=density, skew=skew, seed=3)
    b = jax.random.normal(jax.random.PRNGKey(0), (150, 37))
    got = np.asarray(spmm(csr, b, sched))
    np.testing.assert_allclose(got, _want_spmm(csr, b), rtol=RTOL, atol=ATOL)


def _lengths_csr(lengths, n_cols=60, seed=0):
    rng = np.random.default_rng(seed)
    dense = np.zeros((len(lengths), n_cols), np.float32)
    for r, ln in enumerate(lengths):
        cols = rng.choice(n_cols, size=int(ln), replace=False)
        dense[r, cols] = rng.standard_normal(int(ln))
    return CSR.fromdense(jnp.asarray(dense))


# 64-lane tiles: one window of 72 rows a tile
_WIN = dict(kernel="eb", nnz_tile=64, col_tile=8, group_size=8)
_SPARSE_TAIL = [2] * 100 + [1 if r % 12 == 0 else 0 for r in range(200)]
#: case -> (row lengths, schedule): tiles that fit their windows; n_rows -
#: window unaligned (the last tile's window clamped at n_rows); empty
#: rows planted so tiles span more than a window (the walk); an output
#: block shorter than a window (the walk, statically); a tile's span at
#: the window's edge; a tile of two chunks; a skew layout with heavy
#: tiles; the epilogue, narrowed output and int8 value paths
WINDOW_CASES = {
    "fit": ([3] * 296, KernelSchedule(**_WIN)),
    "clamped": ([3] * 301, KernelSchedule(**_WIN)),
    "empty_rows": (_SPARSE_TAIL, KernelSchedule(**_WIN)),
    "short_block": ([3] * 50, KernelSchedule(**_WIN)),
    # the first tile's last lane in row 71 (inside its window, rows
    # 0-71) or row 72 (just outside)
    "span_71": ([1] * 63 + [0] * 8 + [2] + [3] * 100, KernelSchedule(**_WIN)),
    "span_72": ([1] * 63 + [0] * 9 + [2] + [3] * 100, KernelSchedule(**_WIN)),
    # 256-lane tiles run two 128-lane chunks; the first tile's second
    # chunk spans 384 rows, so that tile walks though its first fits
    "two_chunks": ([1] * 128 + [1 if r % 3 == 0 else 0 for r in range(390)]
                   + [3] * 200,
                   KernelSchedule(kernel="eb", nnz_tile=256, col_tile=8,
                                  group_size=8)),
    "skew": ([40, 33] + [2] * 150 + _SPARSE_TAIL,
             KernelSchedule(**_WIN, split_threshold=16, merge_threshold=2)),
    "bias_relu": ([3] * 301, KernelSchedule(
        **_WIN, epilogue=Epilogue(activation="relu", bias=True))),
    "bf16_out": ([3] * 301, KernelSchedule(
        **_WIN, epilogue=Epilogue(out_dtype="bfloat16"))),
    "int8": (_SPARSE_TAIL, KernelSchedule(**_WIN, value_dtype="int8")),
}


def _window_case(case):
    lengths, sched = WINDOW_CASES[case]
    csr = _lengths_csr(lengths)
    b = jax.random.normal(jax.random.PRNGKey(4), (60, 13))
    bias = jax.random.normal(jax.random.PRNGKey(5), (13,))
    return csr, b, (bias if sched.epilogue.bias else None), sched


def _launch_lanes(csr, b, sched):
    """The lanes an eb launch of ``sched`` runs over ``csr``: its
    GroupedCOO, each lane's float32 value, and the dense operand."""
    if sched.value_dtype == "int8":
        q = csr.quantized()
        g = q.csr.grouped(sched.nnz_tile)
        vals = (np.asarray(g.vals, np.float32)
                * np.asarray(q.scales)[np.asarray(g.rows)])
        return g, vals, b.astype(jnp.bfloat16).astype(jnp.float32)
    skew = dict(group_size=sched.group_size,
                split_threshold=sched.split_threshold,
                merge_threshold=sched.merge_threshold)
    g = csr.grouped(sched.nnz_tile, **(skew if sched.is_skew else {}))
    return g, np.asarray(g.vals, np.float32), b


def _lanes_ref(g, vals, b, sched, bias, keep=None):
    """Dense reference over the launch's lanes (those of the tiles in
    ``keep`` alone, if given), with the schedule's epilogue."""
    if keep is not None:
        vals = vals * np.repeat(keep, g.nnz_tile)
    out = ref.spmm_coo_ref(g.rows, g.cols, jnp.asarray(vals), b, g.shape[0])
    if sched.epilogue.is_noop:
        return out
    return sched.epilogue.apply(out, bias=bias)


@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_spmm_eb_window_matches_reference(case):
    csr, b, bias, sched = _window_case(case)
    got = np.asarray(spmm(csr, b, sched, bias=bias), np.float32)
    want = np.asarray(_lanes_ref(*_launch_lanes(csr, b, sched), sched, bias),
                      np.float32)
    tol = 1e-2 if sched.epilogue.out_dtype else RTOL
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def _no_walk(monkeypatch):
    """Take the run walk (and every registry realization) out of the eb
    kernel: a tile that does not take the window then adds nothing."""
    import importlib

    from repro.kernels import common

    # the package's ``spmm_eb`` is the kernel's function; this is its module
    eb = importlib.import_module("repro.kernels.spmm_eb")

    def skip(*args, **kwargs):
        del args, kwargs

    monkeypatch.setattr(common, "group_reduce_scatter", skip)
    monkeypatch.setattr(eb, "group_reduce_scatter", skip)
    jax.clear_caches()


@pytest.mark.parametrize("case", list(WINDOW_CASES))
def test_spmm_eb_window_count_agrees_with_kernel(case, monkeypatch):
    """The host's count of the tiles that take the window
    (``ops.eb_window_tiles``) is the kernel's choice: without the walk,
    the kernel's output is the reference over exactly those tiles'
    lanes."""
    from repro.kernels import ops

    csr, b, bias, sched = _window_case(case)
    g, vals, b_used = _launch_lanes(csr, b, sched)
    keep = ops.eb_window_tiles(g, sched.strategy)
    assert (g.heavy_tiles > 0) == (case == "skew")
    if case.startswith("span_"):
        assert np.asarray(g.rows)[63] == int(case[-2:])
    # planted empty rows and heavy tiles leave some tiles to the walk
    some_walk = case in ("empty_rows", "skew", "int8", "short_block",
                         "span_72", "two_chunks")
    assert keep.any() == (case != "short_block")
    assert keep.all() == (not some_walk)
    _no_walk(monkeypatch)
    try:
        got = np.asarray(spmm(csr, b, sched, bias=bias), np.float32)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    want = np.asarray(_lanes_ref(g, vals, b_used, sched, bias, keep),
                      np.float32)
    tol = 1e-2 if sched.epilogue.out_dtype else RTOL
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def _unsorted_lanes(case):
    """(rows, cols, vals) of 64-lane tiles over 200 rows, two lanes a row
    for rows 0-191, in an order other than row-sorted: each tile's lanes
    shuffled (every tile still spans 32 rows); one lane of the first
    tile moved to row 150, between a first and a last lane that lie
    inside the tile's window; or all lanes shuffled across tiles."""
    rng = np.random.default_rng(7)
    rows = np.repeat(np.arange(192, dtype=np.int32), 2)
    if case == "shuffled_in_tile":
        rows = rng.permuted(rows.reshape(-1, 64), axis=1).reshape(-1)
    elif case == "middle_lane_outside":
        rows[30] = 150
    else:
        rows = rng.permutation(rows)
    cols = rng.integers(0, 60, rows.size).astype(np.int32)
    vals = rng.standard_normal(rows.size).astype(np.float32)
    return rows, cols, vals


@pytest.mark.parametrize("walk", [True, False], ids=["walk", "no_walk"])
@pytest.mark.parametrize("case", ["shuffled_in_tile", "middle_lane_outside",
                                  "shuffled_everywhere"])
def test_spmm_eb_window_unsorted_lanes(case, walk, monkeypatch):
    """Lanes in any order: a tile takes the window only where every lane's
    row lies inside it, and the kernel's sum is the reference's.  Without
    the walk, the output is the reference over those tiles alone."""
    from repro.kernels.common import segment_window, window_start
    from repro.kernels.spmm_eb import spmm_eb as kernel

    rows, cols, vals = _unsorted_lanes(case)
    b = jax.random.normal(jax.random.PRNGKey(6), (60, 16))
    lanes, w = segment_window("segment", 200, 64)
    tiles = rows.reshape(-1, lanes)
    _, keep = window_start(tiles.min(axis=1), tiles.max(axis=1), 200, w, xp=np)
    assert keep.all() == (case == "shuffled_in_tile")
    assert keep[0] == (case == "shuffled_in_tile")
    want_vals = vals if walk else vals * np.repeat(keep, 64)
    if not walk:
        _no_walk(monkeypatch)
    try:
        got = kernel(jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals),
                     b, n_rows=200, nnz_tile=64, col_tile=8, group_size=8)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    want = ref.spmm_coo_ref(jnp.asarray(rows), jnp.asarray(cols),
                            jnp.asarray(want_vals), b, 200)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=RTOL, atol=ATOL)


def test_make_spmm_pallas_unsorted_triplets():
    """``make_spmm(impl='pallas')`` over caller triplets in no row order
    (an A-transpose stream): the forward and both gradients match the
    reference implementation."""
    from repro.sparse.autodiff import make_spmm

    rows, cols, vals = _unsorted_lanes("shuffled_everywhere")
    rows, cols = jnp.asarray(rows), jnp.asarray(cols)
    b = jax.random.normal(jax.random.PRNGKey(8), (60, 8))
    fns = [make_spmm(rows, cols, 200, 60, impl=impl) for impl in ("pallas", "ref")]
    outs = [jax.value_and_grad(lambda v, x, f=f: jnp.sum(f(v, x) ** 2),
                               argnums=(0, 1))(jnp.asarray(vals), b)
            for f in fns]
    for got, want in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("n_rows,n_cols,n_dense", [(100, 80, 20), (64, 64, 8), (33, 70, 130)])
@pytest.mark.parametrize("row_tile", [4, 8, 16])
def test_spmm_rb_shape_sweep(n_rows, n_cols, n_dense, row_tile):
    csr = random_csr(n_rows, n_cols, density=0.05, seed=7)
    b = jax.random.normal(jax.random.PRNGKey(1), (n_cols, n_dense))
    sched = KernelSchedule("rb", row_tile=row_tile, col_tile=8)
    got = np.asarray(spmm(csr, b, sched))
    np.testing.assert_allclose(got, _want_spmm(csr, b), rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_dtypes(dtype):
    csr = random_csr(96, 96, density=0.03, seed=11)
    csr = type(csr)(indptr=csr.indptr, indices=csr.indices,
                    vals=csr.vals.astype(dtype), shape=csr.shape)
    b = jax.random.normal(jax.random.PRNGKey(2), (96, 16)).astype(dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else RTOL
    got = np.asarray(spmm(csr, b, KernelSchedule("eb", nnz_tile=64,
                                                 col_tile=8, group_size=8)))
    np.testing.assert_allclose(got, _want_spmm(csr, b), rtol=tol, atol=tol)


def test_spmm_empty_rows_and_single_tile():
    # matrix with many empty rows, nnz < one tile
    csr = random_csr(50, 40, density=0.002, seed=13)
    b = jax.random.normal(jax.random.PRNGKey(3), (40, 4))
    got = np.asarray(spmm(csr, b, KernelSchedule("eb", nnz_tile=64,
                                                 col_tile=8, group_size=8)))
    np.testing.assert_allclose(got, _want_spmm(csr, b), rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("d", [16, 33, 128])
def test_sddmm(d):
    csr = random_csr(100, 80, density=0.05, seed=5)
    coo = csr.tocoo()
    a = jax.random.normal(jax.random.PRNGKey(2), (100, d))
    b = jax.random.normal(jax.random.PRNGKey(3), (80, d))
    want = np.asarray(ref.sddmm_ref(coo.rows, coo.cols, a, b))
    got = np.asarray(sddmm(coo.rows, coo.cols, a, b, nnz_tile=64))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_sddmm_with_scale():
    csr = random_csr(60, 60, density=0.05, seed=6)
    coo = csr.tocoo()
    a = jax.random.normal(jax.random.PRNGKey(4), (60, 24))
    b = jax.random.normal(jax.random.PRNGKey(5), (60, 24))
    want = np.asarray(ref.sddmm_ref(coo.rows, coo.cols, a, b, coo.vals))
    got = np.asarray(sddmm(coo.rows, coo.cols, a, b, coo.vals, nnz_tile=64))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("group_size", [8, 16, 32, 64])
@pytest.mark.parametrize("strategy", ["segment", "accumulate"])
def test_segment_reduce_kernel(group_size, strategy):
    T, C, S = 256, 16, 40
    rng = np.random.default_rng(0)
    seg = np.sort(rng.integers(0, S, T)).astype(np.int32)
    data = rng.standard_normal((T, C)).astype(np.float32)
    want = np.asarray(ref.segment_reduce_ref(jnp.asarray(data),
                                             jnp.asarray(seg), S))
    got = np.asarray(
        segment_reduce(jnp.asarray(seg), jnp.asarray(data), num_segments=S,
                       tile=max(64, group_size), group_size=group_size,
                       strategy=strategy))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("group_size", [2, 4, 8, 16, 32])
def test_segment_group_reduce_spec_matches_segment_sum(group_size):
    T, C, S = 128, 8, 50
    rng = np.random.default_rng(1)
    seg = np.sort(rng.integers(0, S, T)).astype(np.int32)
    data = rng.standard_normal((T, C)).astype(np.float32)
    want = np.asarray(ref.segment_reduce_ref(jnp.asarray(data), jnp.asarray(seg), S))
    got = np.asarray(segment_group_reduce(
        jnp.asarray(data), jnp.asarray(seg), S, group_size=group_size,
        strategy=GroupReduceStrategy.SEGMENT))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_segment_group_parallel_contract():
    """PARALLEL strategy: groups whose lanes share one segment reduce
    exactly; the contract holds when seg ids are constant per group."""
    G, n_groups, C = 8, 6, 4
    seg = np.repeat(np.arange(n_groups), G).astype(np.int32)
    data = np.random.default_rng(2).standard_normal((G * n_groups, C)).astype(np.float32)
    got = np.asarray(segment_group_reduce(
        jnp.asarray(data), jnp.asarray(seg), n_groups, group_size=G,
        strategy=GroupReduceStrategy.PARALLEL))
    want = data.reshape(n_groups, G, C).sum(1)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("gs", [[40, 0, 70, 17], [1, 1, 1, 1], [0, 0, 128, 0]])
def test_grouped_matmul(gs):
    E, D, F, TT = 4, 64, 96, 32
    gs = np.asarray(gs)
    tiles = expert_tile_map(gs, TT)
    if len(tiles) == 0:
        pytest.skip("no tokens")
    t_pad = len(tiles) * TT
    rng = np.random.default_rng(2)
    x = rng.standard_normal((t_pad, D)).astype(np.float32)
    eids = np.repeat(tiles, TT)
    w = rng.standard_normal((E, D, F)).astype(np.float32)
    want = np.asarray(ref.grouped_matmul_ref(jnp.asarray(x), jnp.asarray(eids),
                                             jnp.asarray(w)))
    got = np.asarray(grouped_matmul(jnp.asarray(x), jnp.asarray(tiles),
                                    jnp.asarray(w), token_tile=TT,
                                    f_tile=32, d_tile=32))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
