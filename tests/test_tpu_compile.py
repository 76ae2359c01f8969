"""Compile the GCN and GAT paths' Pallas kernels for a described TPU v5e
at the widths of Planetoid PubMed, and the windowed eb launch at those of
ogbn-arxiv — no chip needed.

Mosaic refuses patterns the interpreter accepts (gathers from VMEM
values, dynamic slices of values, 1-D blocks that disagree with XLA's
HBM tiling, more VMEM than a kernel asked for), so these compiles guard
the main path between chip runs.  The topology is described inside a
fixture: only the worker that runs this file loads the TPU compiler.
"""
import functools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.schedule import Epilogue, Schedule
from repro.kernels import common
from repro.kernels.ops import vmem_footprint_eb
from repro.kernels.segment_reduce import segment_reduce
from repro.kernels.spmm_eb import spmm_eb, spmm_eb_stream, vmem_need_eb
from repro.kernels.spmm_rb import spmm_rb
from repro.sparse.formats import round_up
from repro.sparse.random import PUBMED, gcn_graph_csr

HIDDEN = 16  # Kipf & Welling's hidden width: the first layer's dense operand
NNZ_TILE = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def pubmed():
    """(n_nodes, nnz, ELL width) of the generated PubMed-scale graph."""
    g = gcn_graph_csr(PUBMED["n_nodes"], PUBMED["n_edges"], seed=0)
    width = int(np.max(np.diff(np.asarray(g.indptr))))
    return PUBMED["n_nodes"], int(g.nnz), width


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _eb_shapes(one_chip, n_nodes, nnz, width):
    nnz_pad = round_up(nnz, NNZ_TILE)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    return (s((nnz_pad,), jnp.int32), s((nnz_pad,), jnp.int32),
            s((nnz_pad,), jnp.float32), s((n_nodes, width), jnp.float32))


@pytest.mark.parametrize("strategy", ["segment", "parallel", "accumulate"])
def test_spmm_eb_compiles(one_chip, pubmed, strategy):
    n_nodes, nnz, _ = pubmed
    fn = functools.partial(spmm_eb, n_rows=n_nodes, nnz_tile=NNZ_TILE,
                           col_tile=HIDDEN, group_size=32, strategy=strategy,
                           interpret=False)
    _assert_kernel(_compile(fn, *_eb_shapes(one_chip, n_nodes, nnz, HIDDEN)))


def test_spmm_eb_bias_relu_epilogue_compiles(one_chip, pubmed):
    n_nodes, nnz, _ = pubmed
    ep = Epilogue(activation="relu", bias=True)

    def fn(rows, cols, vals, b, bias):
        return spmm_eb(rows, cols, vals, b, n_rows=n_nodes,
                       nnz_tile=NNZ_TILE, col_tile=HIDDEN, epilogue=ep,
                       bias=bias, interpret=False)

    bias = jax.ShapeDtypeStruct((1, HIDDEN), jnp.float32, sharding=one_chip)
    _assert_kernel(_compile(
        fn, *_eb_shapes(one_chip, n_nodes, nnz, HIDDEN), bias))


def test_spmm_rb_compiles(one_chip, pubmed):
    n_nodes, _, width = pubmed
    r_pad = round_up(n_nodes, 8)
    fn = functools.partial(spmm_rb, row_tile=8, col_tile=HIDDEN,
                           interpret=False)
    _assert_kernel(_compile(
        fn,
        jax.ShapeDtypeStruct((r_pad, width), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((r_pad, width), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n_nodes, HIDDEN), jnp.float32,
                             sharding=one_chip)))


def test_segment_reduce_compiles(one_chip, pubmed):
    n_nodes, nnz, _ = pubmed
    fn = functools.partial(segment_reduce, num_segments=n_nodes,
                           tile=NNZ_TILE, group_size=32, interpret=False)
    _assert_kernel(_compile(
        fn,
        jax.ShapeDtypeStruct((nnz,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((nnz, HIDDEN), jnp.float32, sharding=one_chip)))


def _compiles_within(fn, shapes, monkeypatch, limit):
    """Whether the launch compiles with its scoped VMEM limit set to
    ``limit`` bytes (no headroom); a scoped-VMEM refusal is False."""
    monkeypatch.setattr(common, "VMEM_HEADROOM", 0)
    monkeypatch.setattr(common, "VMEM_CAPACITY", limit)
    jax.clear_caches()  # the limit is baked into the traced launch
    try:
        _compile(fn, *shapes)
    except Exception as e:
        if "Scoped allocation" not in str(e):
            raise
        return False
    return True


@pytest.mark.parametrize("width,col_tile", [(256, 128), (128, 128), (16, 16)])
def test_vmem_footprint_matches_compiler(one_chip, pubmed, monkeypatch,
                                         width, col_tile):
    """The eb VMEM count (padded tiles, pipeline buffers) is the
    compiler's scoped allocation to within one (1, 128) row: the launch
    compiles under a limit of exactly that many bytes and is refused
    under 512 fewer.  Double-buffered blocks when B spans column tiles
    (the tuner's ``vmem_footprint_eb``), single-buffered when one tile
    covers it.  At 16 columns (the GCN's hidden width) XLA places B and
    the output in VMEM itself (``S(1)``), so only the kernel's
    partials scratch is scoped — see ``spmm_eb.vmem_need_eb``."""
    n_nodes, nnz, _ = pubmed
    fn = functools.partial(spmm_eb, n_rows=n_nodes, nnz_tile=NNZ_TILE,
                           col_tile=col_tile, interpret=False)
    shapes = _eb_shapes(one_chip, n_nodes, nnz, width)
    compiled = _compile(fn, *shapes)
    _assert_kernel(compiled)
    xla_placed = "S(1)" in compiled.as_text()
    assert xla_placed == (width == 16)
    if xla_placed:
        scoped = common.vmem_bytes((NNZ_TILE, 128), jnp.float32)
    elif width == col_tile:
        scoped = vmem_need_eb(n_nodes, n_nodes, nnz_tile=NNZ_TILE,
                              col_tile=col_tile, n=width)
    else:
        scoped = vmem_footprint_eb(
            n_nodes, n_nodes, Schedule(nnz_tile=NNZ_TILE, col_tile=col_tile))
    assert _compiles_within(fn, shapes, monkeypatch, scoped)
    assert not _compiles_within(fn, shapes, monkeypatch, scoped - 512)
    jax.clear_caches()


#: ogbn-arxiv's adjacency (169,343 rows, 2,484,941 entries with the
#: self-loops) in 128-lane tiles, as ``Schedule.auto`` feeds OGB's GCN,
#: with room for each block's padding
ARXIV_ROWS, ARXIV_TILES = 169343, 19500


@pytest.mark.parametrize("width", [256, 40])
def test_stream_vmem_matches_compiler(one_chip, monkeypatch, width):
    """The windowed launch ``spmm_eb_stream`` at ogbn-arxiv's size, in the
    windows ``eb_stream_windows`` picks for OGB's GCN widths (two column
    tiles of 256, or one of 40, with the bias epilogue), compiles, and its
    ``vmem_need_eb`` count is the compiler's scoped allocation to within
    the launch's small blocks: it compiles under exactly that many bytes,
    and is refused under that less the value and row-id lane blocks, the
    bias block and 512.  (At PubMed's size the count is exact for this
    launch too; at ogbn-arxiv's the compiler leaves those few KiB out of
    its scoped allocation.)"""
    from repro.kernels.ops import _col_tile, eb_stream_windows

    sched = Schedule("eb", nnz_tile=128, col_tile=128, group_size=16,
                     epilogue=Epilogue(bias=True))
    windows = eb_stream_windows((ARXIV_ROWS, ARXIV_ROWS), width, sched)
    assert windows is not None
    col_tile = _col_tile(sched, width)
    n = round_up(width, col_tile)

    def fn(rows, cols, vals, tile_rows, tile_cols, b, bias):
        return spmm_eb_stream(rows, cols, vals, tile_rows, tile_cols, b,
                              n_rows=ARXIV_ROWS, row_window=windows[0],
                              col_window=windows[1], nnz_tile=128,
                              col_tile=col_tile, group_size=16,
                              epilogue=sched.epilogue, bias=bias,
                              interpret=False)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lanes = ARXIV_TILES * 128
    shapes = (s((lanes,), jnp.int32), s((lanes,), jnp.int32),
              s((lanes,), jnp.float32), s((ARXIV_TILES,), jnp.int32),
              s((ARXIV_TILES,), jnp.int32), s((ARXIV_ROWS, n), jnp.float32),
              s((1, n), jnp.float32))
    compiled = _compile(fn, *shapes)
    _assert_kernel(compiled)
    assert "spmm_eb_stream" in compiled.as_text()
    scoped = vmem_need_eb(ARXIV_ROWS, ARXIV_ROWS, nnz_tile=128,
                          col_tile=col_tile, n=n, epilogue=sched.epilogue,
                          windows=windows)
    small = (2 * common.vmem_bytes((1, 128), jnp.float32, 2)
             + common.vmem_bytes((1, col_tile), jnp.float32, 2 if n > col_tile else 1))
    assert _compiles_within(fn, shapes, monkeypatch, scoped)
    assert not _compiles_within(fn, shapes, monkeypatch, scoped - small - 512)
    jax.clear_caches()


def test_deep_gcn_step_runs_windowed_launches(one_chip, monkeypatch):
    """The deep GCN training cell's step (``bench/modes/train_gcn_deep.py``)
    at a cut size whose launches run windowed (a VMEM budget lowered for
    the test), compiled for the described chip: one forward launch a
    layer, each ``spmm_eb_stream``, and no resident launch."""
    import json

    from bench import trace
    from bench.modes import train as loop
    from bench.modes import train_gcn_deep as mode
    from bench.run import ROOT
    from bench.traffic import gcn as graphs
    from bench.traffic import gcn_deep as traffic
    from repro.kernels import ops as kops

    cfg = json.loads((ROOT / "bench" / "configs" / "gcn-arxiv.json").read_text())
    cfg.update(n_nodes=300, n_edges=700, n_entries=1700, n_features=40,
               hidden=32, n_classes=5, train_nodes=150)
    monkeypatch.setattr(kops, "_VMEM_BUDGET", 250_000)
    graph = graphs.config_graph(cfg)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        inputs = traffic.make_inputs(cfg, graph, 7)
        program = mode.build_program(cfg, graph, inputs)
        on_chip = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
        args = (inputs["params"],
                mode.initial_state(program, inputs, inputs["params"]),
                *loop.feed(inputs))
        monkeypatch.setattr(common, "pallas_interpret_default", lambda: False)
        jax.clear_caches()
        try:
            hlo = _compile(program["step"], *on_chip(args)).as_text()
        finally:
            monkeypatch.undo()
            jax.clear_caches()
    launches = trace.pallas_launches(hlo)
    assert [(lc["kernel"], lc["backward"]) for lc in launches] == [("spmm_eb", False)] * 3
    assert all(lc["name"].startswith("spmm_eb_stream") for lc in launches)


def test_gcn_step_launches_are_named(one_chip, monkeypatch):
    """The training cell's GCN step (``bench/modes/train.py``) at a cut
    size, compiled for the described chip: its two forward launches run
    ``spmm_eb``, named so in the compiled program, and the scopes the
    program names do not hide them from ``bench.trace.pallas_launches``;
    its backward scatters only in the two transpose SpMMs."""
    import json

    from bench import trace
    from bench.modes import train
    from bench.run import ROOT
    from bench.traffic import gcn as traffic

    cfg = json.loads((ROOT / "bench" / "configs" / "gcn-pubmed.json").read_text())
    cfg.update(n_nodes=300, n_edges=700, n_entries=1700, n_features=40)
    graph = traffic.config_graph(cfg)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        program = train.build_program(cfg, graph)
        inputs = traffic.make_inputs(cfg, graph, 7)
        on_chip = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
        args = (inputs["params"], program["opt"].init(inputs["params"]),
                *train.feed(inputs))
        # the eager forward above ran interpreted; the step's kernels
        # are traced again, for the chip
        monkeypatch.setattr(common, "pallas_interpret_default", lambda: False)
        jax.clear_caches()
        try:
            hlo = _compile(program["step"], *on_chip(args)).as_text()
        finally:
            monkeypatch.undo()
            jax.clear_caches()
    launches = trace.pallas_launches(hlo)
    assert [(lc["kernel"], lc["backward"]) for lc in launches] == [("spmm_eb", False)] * 2
    assert all(lc["name"].startswith("spmm_eb") for lc in launches)
    # the first launch's ReLU takes its derivative from the saved output:
    # the backward's scatters are the two transpose SpMMs alone
    bwd = [ln for ln in hlo.splitlines()
           if " scatter(" in ln and re.search(r'op_name="[^"]*spmm\.bwd/', ln)]
    assert len(bwd) == 2 and all("/spmm.bwd/tspmm/" in ln for ln in bwd)


#: GAT-PubMed (Veličković et al.): 8 heads; value widths 8 (the first
#: layer) and 3 (the output layer's classes)
GAT_HEADS = 8


@pytest.mark.parametrize("width", [8, 3])
def test_fused_attention_compiles(one_chip, pubmed, width):
    """The fused attention forward (two-phase, and one pass with a shift
    table) and backward, additive score with a keep mask, compile to
    ``tpu_custom_call`` at GAT-PubMed shapes: 19,717 rows, 108,393
    entries, 8 heads of ``width`` values."""
    from repro.kernels.fused_attention import (
        fused_sparse_attention,
        fused_sparse_attention_bwd,
    )

    n, nnz, _ = pubmed
    nnz_pad = round_up(nnz, NNZ_TILE)
    kw = dict(n_rows=n, nnz=nnz, nnz_tile=NNZ_TILE, score="additive",
              interpret=False)

    def s(*shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lanes = (s(nnz_pad, dt=jnp.int32), s(nnz_pad, dt=jnp.int32))
    terms = (s(n, GAT_HEADS, 1), s(n, GAT_HEADS, 1))
    vals = s(n, GAT_HEADS, width)
    keep = s(nnz_pad, GAT_HEADS)
    stats = (s(n, GAT_HEADS), s(n, GAT_HEADS))

    def fwd(r, c, q, k, v, kp):
        return fused_sparse_attention(r, c, q, k, v, keep=kp, **kw)

    def one_pass(r, c, q, k, v, kp, m):
        return fused_sparse_attention(r, c, q, k, v, keep=kp, shift=m, **kw)

    def bwd(r, c, q, k, v, o, do, m, l, kp):
        return fused_sparse_attention_bwd(r, c, q, k, v, o, do, m, l, keep=kp,
                                          **kw)

    for fn, shapes, name in (
            (fwd, (*lanes, *terms, vals, keep), "fused_attention_fwd"),
            (one_pass, (*lanes, *terms, vals, keep, stats[0]),
             "fused_attention_fwd"),
            (bwd, (*lanes, *terms, vals, vals, vals, *stats, keep),
             "fused_attention_bwd")):
        compiled = _compile(fn, *shapes)
        _assert_kernel(compiled)
        assert name in compiled.as_text()


def test_gat_step_has_its_four_attention_launches(one_chip, monkeypatch):
    """The GAT training cell's step (``bench/modes/train_gat.py``) at a cut
    size, compiled for the described chip: exactly four Pallas launches,
    each layer's fused attention forward and backward, named so, and no
    interpreted kernel; each layer's softmax shift stays a conditional
    whose bound and exact branches ``attn_bound_share`` finds (not a
    select that would run both), each counted by a twin operation of the
    same opcode and shape that is not a fusion."""
    import json

    from bench import counts_gat
    from bench.metrics import attn_bound_share
    from bench.modes import train, train_gat
    from bench.run import ROOT
    from bench.traffic import gat as traffic
    from bench.traffic import gcn

    cfg = json.loads((ROOT / "bench" / "configs" / "gat-pubmed.json").read_text())
    cfg.update(n_nodes=300, n_edges=700, n_entries=1700, n_features=40)
    graph = gcn.config_graph(cfg)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        program = train_gat.build_program(cfg, graph)
        inputs = traffic.make_inputs(cfg, graph, 7)
        on_chip = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
        args = (inputs["params"], program["opt"].init(inputs["params"]),
                *train.feed(inputs))
        monkeypatch.setattr(common, "pallas_interpret_default", lambda: False)
        jax.clear_caches()
        try:
            hlo = _compile(program["step"], *on_chip(args)).as_text()
        finally:
            monkeypatch.undo()
            jax.clear_caches()
    launches = counts_gat.attn_launches(hlo)
    assert sorted(lc["kernel"] for lc in launches) == sorted(
        counts_gat.ATTN_KERNELS * 2)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 4
    layers = attn_bound_share.shift_branches(hlo)
    assert len(layers) == 2
    for layer in layers:
        ops = {op[0]: op[1:] for branch in layer.values() for op in branch}
        mark = attn_bound_share.markers(layer)
        assert ops[mark["bound"]] == ops[mark["exact"]]
        assert ops[mark["bound"]][1] != "fusion"
