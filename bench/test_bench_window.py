"""``spmm_window_share``: the program's count of the eb tiles whose
'segment' reduce runs as MXU window products, read by the benchmark."""
import json

import numpy as np
import pytest

from bench.metrics import spmm_window_share
from bench.run import ROOT
from bench.traffic import gcn as traffic


def _config(cell):
    return json.loads((ROOT / "bench" / "configs" / f"{cell}.json").read_text())


def test_pubmed_graph_takes_the_window_in_every_tile():
    """The PubMed cell's graph under the schedule its selector picks:
    self-loops leave no empty row, so every 128-lane tile spans at most
    128 rows and fits its window."""
    import jax.numpy as jnp

    from repro.kernels.ops import eb_window_tiles
    from repro.sparse import CSR, Schedule, matrix_stats

    cfg = _config("gcn-pubmed")
    graph = traffic.config_graph(cfg)
    adj = CSR(indptr=jnp.asarray(graph["indptr"]),
              indices=jnp.asarray(graph["indices"]),
              vals=jnp.asarray(graph["vals"]), shape=graph["shape"])
    sched = Schedule.auto(matrix_stats(adj), cfg["hidden"])
    assert (sched.kernel, sched.nnz_tile, sched.strategy) == ("eb", 128, "segment")
    fits = eb_window_tiles(adj.grouped(128), "segment")
    assert fits.size == 847 and fits.all()
    assert spmm_window_share.read({"config": cfg}) == 100.0


def _planted_graph():
    """Rows 100-339 of a 340-row matrix hold one entry each 12 rows: the
    64-lane tile that spans them covers more rows than its window."""
    rng = np.random.default_rng(0)
    indptr, indices = [0], []
    for r in range(340):
        k = 2 if r < 100 else int(r % 12 == 0)
        indices += sorted(rng.choice(40, k, replace=False).tolist())
        indptr.append(len(indices))
    return {"indptr": np.asarray(indptr, np.int32),
            "indices": np.asarray(indices, np.int32),
            "vals": np.ones(len(indices), np.float32), "shape": (340, 40)}


def test_planted_empty_rows_leave_a_tile_to_the_walk(monkeypatch):
    from repro.kernels.ops import eb_window_tiles
    from repro.sparse import CSR, Schedule

    graph = _planted_graph()
    sched = Schedule("eb", nnz_tile=64, col_tile=8, group_size=8)
    monkeypatch.setattr(traffic, "config_graph", lambda cfg: graph)
    monkeypatch.setattr(Schedule, "auto", staticmethod(lambda *a, **k: sched))
    csr = CSR(indptr=graph["indptr"], indices=graph["indices"],
              vals=graph["vals"], shape=graph["shape"])
    fits = eb_window_tiles(csr.grouped(64), "segment")
    assert fits.any() and not fits.all()
    got = spmm_window_share.read({"config": {"hidden": 8}})
    assert got == pytest.approx(100.0 * fits.mean())


def test_nothing_to_read_without_an_eb_launch(monkeypatch):
    """A schedule that runs no eb launch, or a program that keeps no
    count: the metric is left out."""
    from repro.kernels import ops
    from repro.sparse import Schedule

    cfg = _config("gcn-cora")
    assert spmm_window_share.read({"config": cfg}) == 100.0
    with monkeypatch.context() as m:
        m.setattr(Schedule, "auto", staticmethod(
            lambda *a, **k: Schedule("rb", row_tile=8, col_tile=8)))
        assert spmm_window_share.read({"config": cfg}) is None
    monkeypatch.delattr(ops, "eb_window_tiles")
    assert spmm_window_share.read({"config": cfg}) is None
