"""The traffic generators, and that everything BENCHMARK.json names is
found by name and builds at a cut size."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.run import BENCH, ROOT, cell_spec, load_module
from bench.traffic import gcn

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
CUT = dict(n_nodes=300, n_edges=700, n_entries=1700, n_features=40)


@pytest.mark.parametrize("name", [c["name"] for c in BENCHMARK["configs"]])
def test_config_graph_has_the_published_counts(name):
    """Config graph has the published counts."""
    cfg = json.loads((ROOT / next(c["file"] for c in BENCHMARK["configs"]
                                  if c["name"] == name)).read_text())
    g = gcn.config_graph(cfg)
    n = cfg["n_nodes"]
    assert g["shape"] == (n, n)
    assert len(g["vals"]) == 2 * cfg["n_edges"] + n == cfg["n_entries"]
    assert g["indptr"][-1] == cfg["n_entries"]
    rows, cols = g["rows"].astype(np.int64), g["indices"].astype(np.int64)
    assert np.array_equal(np.repeat(np.arange(n), np.diff(g["indptr"])), rows)
    keys = rows * n + cols
    assert np.all(np.diff(keys) > 0)  # rows sorted, columns sorted in a row
    assert np.array_equal(np.sort(cols * n + rows), keys)  # symmetric
    assert np.all(g["vals"][rows == cols] > 0)  # every self-loop is there


def test_same_seed_same_graph_and_other_seed_other():
    """Same seed same graph and other seed other."""
    a, b = gcn.gcn_graph(400, 900, seed=7), gcn.gcn_graph(400, 900, seed=7)
    c = gcn.gcn_graph(400, 900, seed=8)
    for k in ("indptr", "indices", "vals"):
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["indices"], c["indices"])


def test_copy_matches_the_program_generator():
    """Copy matches the program generator."""
    from repro.sparse.random import gcn_graph_csr

    g, p = gcn.gcn_graph(400, 900, seed=5), gcn_graph_csr(400, 900, seed=5)
    assert np.array_equal(g["indptr"], np.asarray(p.indptr))
    assert np.array_equal(g["indices"], np.asarray(p.indices))
    assert np.array_equal(g["vals"], np.asarray(p.vals))


def test_large_seeds_give_distinct_repeatable_keys():
    """Large seeds give distinct repeatable keys."""
    big = 2**31 + 12345
    assert np.array_equal(gcn.seed_key(big), gcn.seed_key(big))
    assert not np.array_equal(gcn.seed_key(big), gcn.seed_key(big + 2**32))
    with pytest.raises(ValueError):
        gcn.seed_key(-1)


@pytest.mark.parametrize("cell", CELLS)
def test_every_named_cell_loads_by_name_and_builds_cut(cell):
    """Every named cell loads by name and builds cut."""
    spec = cell_spec(cell)
    assert spec["cell"]["name"] == cell
    assert (BENCH / "modes" / f"{spec['traffic']['mode']}.py").is_file()
    assert set(spec["limits"]) == {"loss", "grad", "update", "loss_3", "update_3"}
    cfg = {**spec["config"], **CUT}
    inputs = gcn.make_inputs(cfg, gcn.config_graph(cfg), seed=2**31 + 99)
    assert inputs["x"].shape == (300, 40)
    train = np.asarray(inputs["train"])
    y = np.asarray(inputs["y"])
    assert len(train) == cfg["n_classes"] * cfg["train_per_class"]
    assert len(set(train.tolist())) == len(train)
    assert np.array_equal(np.bincount(y[train], minlength=cfg["n_classes"]),
                          np.full(cfg["n_classes"], cfg["train_per_class"]))
    again = gcn.make_inputs(cfg, gcn.config_graph(cfg), seed=2**31 + 99)
    assert np.array_equal(np.asarray(again["x"]), np.asarray(inputs["x"]))
    assert np.array_equal(np.asarray(again["train"]), train)


def test_every_cell_file_is_a_cell_of_the_benchmark():
    """The cells' own files are those of the cells BENCHMARK.json names."""
    cells = {p.stem for p in (BENCH / "workloads").glob("*.json")}
    assert cells == set(CELLS)


def test_every_named_metric_has_a_reader_and_every_reader_a_name():
    """Every named metric has a reader and every reader a name."""
    names = {m["name"] for m in BENCHMARK["per_layer"]}
    files = {p.stem for p in (BENCH / "metrics").glob("*.py")} - {"__init__"}
    assert names == files
    for name in names:
        assert callable(load_module(BENCH / "metrics" / f"{name}.py").read)


def test_every_file_the_benchmark_names_exists():
    """Every file the benchmark names exists."""
    for c in BENCHMARK["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
        assert c["reduced"] == json.loads((ROOT / c["file"]).read_text())["reduced"]
    for w in BENCHMARK["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "workloads" / f"{w['name']}.json").is_file()
    assert BENCHMARK["paths"] == ["bench"]
    assert Path(ROOT / BENCHMARK["command"][1]).is_file()


@pytest.mark.parametrize("extra", [{"hidden_dropout": 0.5}, {"layers": 3},
                                   {"dtype": "bfloat16"}])
def test_a_setting_the_mode_does_not_run_is_refused(extra):
    """A setting the mode does not run is refused."""
    train = load_module(BENCH / "modes" / "train.py")
    cfg = cell_spec(CELLS[0])["config"]
    train.check_config(cfg)
    with pytest.raises(ValueError, match="does not run|runs float32"):
        train.check_config({**cfg, **extra})
