"""The deep GCN training cell end to end on the CPU at a cut size: a sound
run is correct; a fault under the timed path, each planted fault and the
control fail the cell's limits; the counts of ``bench.counts_gcn_deep``
by hand; the two readers of the windowed launches on small records."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, counts, counts_gcn_deep, reference_gat, reference_gcn_deep
from bench.metrics import stream_bytes_ratio, stream_roofline
from bench.modes import train_gcn_deep as mode
from bench.run import cell_spec, parse, run
from bench.traffic import gcn
from bench.traffic import gcn_deep as traffic

CELL = "gcn-arxiv.train"
CUT = dict(n_nodes=300, n_edges=700, n_entries=1700, n_features=40, hidden=32,
           n_classes=5, train_nodes=150, valid_nodes=50, test_nodes=100)
SEED = 2**31 + 29
ARXIV = dict(n_nodes=169343, n_entries=2484941, n_features=128, hidden=256,
             n_classes=40, layers=3)


def cut_run(**hooks):
    """One run of the cell at a cut size, with no look for a chip."""
    args = parse(["--workload", CELL, "--seed", str(SEED), "--seconds", "0.2",
                  "--trace", "0"])
    return run(args, require_chip=False, resize=CUT, **hooks)


def cut_config():
    return {**cell_spec(CELL)["config"], **CUT}


def test_sound_run_is_correct():
    """Sound run is correct, and reports the end-to-end metrics."""
    line = cut_run()
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"step_ms", "step_p95_ms", "setup_s"}


def test_fault_under_the_timed_path_is_caught():
    """The program's GCN with its logits altered, put under the timed
    path, makes ``correct`` false."""
    from repro.models.layers import gcn as program_gcn

    def altered(*args, **kw):
        logits, stats = program_gcn(*args, **kw)
        return logits.at[:, 0].add(0.05), stats

    assert cut_run(model=altered)["correct"] is False


VARIANTS = {"control_written_out": {"dot": reference_gat.dot_bf16},
            **reference_gcn_deep.FAULTS}


@pytest.mark.parametrize("name", list(VARIANTS))
def test_faults_and_control_fail(name):
    """Each planted fault, and the control's bfloat16 pass, in the
    program's place fail at least one of the cell's limits."""
    cfg = cut_config()
    graph = gcn.config_graph(cfg)
    inputs = traffic.make_inputs(cfg, graph, SEED)
    bad = reference_gcn_deep.first_steps(cfg, graph, inputs, **VARIANTS[name])
    ref = reference_gcn_deep.first_steps(cfg, graph, inputs, follow=bad)
    ok, shown = check.verdict(check.numbers(bad, ref), cell_spec(CELL)["limits"])
    assert not ok, shown


def test_inputs_follow_the_seed_and_the_split_sizes():
    """The training nodes are ``train_nodes`` distinct nodes, the same for
    a seed and other for another; the masks are one function of the key."""
    cfg = cut_config()
    graph = gcn.config_graph(cfg)
    a, b = (traffic.make_inputs(cfg, graph, SEED) for _ in range(2))
    c = traffic.make_inputs(cfg, graph, SEED + 1)
    train = np.asarray(a["train"])
    assert len(train) == len(set(train.tolist())) == cfg["train_nodes"]
    np.testing.assert_array_equal(train, np.asarray(b["train"]))
    assert not np.array_equal(train, np.asarray(c["train"]))
    assert set(a["params"]) == {"w0", "b0", "gamma0", "beta0", "w1", "b1",
                                "gamma1", "beta1", "w2", "b2"}
    assert a["params"]["w1"].shape == (32, 32) and a["params"]["w2"].shape == (32, 5)
    key = gcn.step_key(a["dropout_key"], 2)
    m = traffic.masks(key, cfg)
    assert set(m) == {"keep0", "keep1"} and m["keep0"].shape == (300, 32)
    assert set(np.unique(np.asarray(m["keep0"]))) == {0.0, 2.0}
    np.testing.assert_array_equal(np.asarray(m["keep1"]),
                                  np.asarray(traffic.masks(key, cfg)["keep1"]))


def test_a_setting_the_mode_does_not_run_is_refused():
    """A setting the mode does not run is refused."""
    cfg = cell_spec(CELL)["config"]
    mode.check_config(cfg)
    for extra in ({"input_dropout": 0.5}, {"dtype": "bfloat16"}):
        with pytest.raises(ValueError, match="does not run|runs float32"):
            mode.check_config({**cfg, **extra})


def test_counts_by_hand():
    """The step's model operations and its forward SpMMs' least time at
    ogbn-arxiv's sizes, by hand."""
    n, nnz = 169343, 2484941
    mm = lambda m, k, p: 2 * m * k * p  # noqa: E731
    want = (mm(n, 128, 256) + 2 * nnz * 256 + 2 * nnz * 256 + mm(128, n, 256)
            + mm(n, 256, 256) + 2 * nnz * 256 + 2 * nnz * 256 + mm(256, n, 256)
            + mm(n, 256, 256)
            + mm(n, 256, 40) + 2 * nnz * 40 + 2 * nnz * 40 + mm(256, n, 40)
            + mm(n, 40, 256))
    assert counts_gcn_deep.train_flops(ARXIV) == want
    peak = counts.peaks("TPU v5 lite")
    per = lambda w: max(2 * nnz * w / 197e12, (  # noqa: E731
        nnz * 8 + (n + 1) * 4 + 2 * n * w * 4 + w * 4) / 819e9)
    assert counts_gcn_deep.forward_spmm_least_time_s(ARXIV, peak) == pytest.approx(
        2 * per(256) + per(40), rel=1e-12)


def test_scheduled_bytes_by_hand():
    """Two row windows of two tiles each over three column windows of a
    100-column B (the last 20 rows), one column tile of 128."""
    got = counts_gcn_deep.scheduled_bytes(
        [0, 0, 1, 1], [0, 2, 2, 1], nnz_padded=4 * 64, n_rows=90, n_cols=100,
        windows=(48, 40), n=128, col_tile=128, bias=True, window_reduce=False)
    # B blocks fetched at tiles 0, 1 and 3: 40 + 20 + 40 rows
    assert got == ((100 + 90) * 128 * 4 + 256 * 12 + 128 * 4) + 2 * 4 * 4


PEAK = counts.peaks("TPU v5 lite")
HLO = "\n".join(
    f'  %spmm_eb_stream.{i} = f32[8]{{0}} custom-call(), custom_call_target="tpu_custom_call", '
    f'metadata={{op_name="jit(step)/jvp(fuse.launch{2 * i}.spmm)/spmm_eb_stream/pallas_call"}}'
    for i in range(3))


def test_stream_roofline_reads_the_forward_spmms_over_their_launches():
    """The readers hold the three forward SpMMs' least time against the
    launches' device time a step; without a trace, or for a configuration
    without a layer count, they read nothing."""
    ops = {f"spmm_eb_stream.{i}": 0.01 for i in range(3)}
    rec = {"trace": {"ops": {**ops, "fusion.1": 0.5}}, "hlo": HLO, "steps": 2,
           "config": ARXIV, "peak": PEAK}
    want = counts_gcn_deep.forward_spmm_least_time_s(ARXIV, PEAK) / 0.015 * 100
    assert stream_roofline.read(rec) == pytest.approx(want)
    assert stream_roofline.read({**rec, "trace": None}) is None
    assert stream_roofline.read({**rec, "config": {"n_nodes": 1}}) is None


def test_stream_bytes_ratio_reads_the_partition_the_run_used(monkeypatch):
    """Over a cut graph whose launches run windowed (a budget lowered for
    the test), the ratio is the partition's scheduled bytes over the
    compulsory ones, above 1; where the launches run resident, nothing."""
    from repro.kernels import ops as kops
    from repro.kernels.common import segment_window
    from repro.sparse import CSR, Schedule

    cfg = {**cut_config(), "layers": 3}
    graph = gcn.config_graph(cfg)
    adj = CSR(indptr=jnp.asarray(graph["indptr"]),
              indices=jnp.asarray(graph["indices"]),
              vals=jnp.asarray(graph["vals"]), shape=graph["shape"])
    sched = Schedule("eb", nnz_tile=64, col_tile=16, group_size=8)
    rec = {"config": cfg, "adjacency": adj, "schedule": sched}
    assert stream_bytes_ratio.read(rec) is None
    monkeypatch.setattr(kops, "_VMEM_BUDGET", 250_000)
    ratio = stream_bytes_ratio.read(rec)
    blk, _ = kops.eb_stream_layout(adj, 32, sched)
    assert blk.windows[0] < 300 and blk.windows[1] < 300
    assert ratio > 1.0
    moved = 0
    for w in (32, 32, 5):
        blk, col_tile = kops.eb_stream_layout(adj, w, sched)
        moved += counts_gcn_deep.scheduled_bytes(
            blk.tile_rows, blk.tile_cols, nnz_padded=blk.nnz_padded,
            n_rows=300, n_cols=300, windows=blk.windows, n=w,
            col_tile=col_tile, bias=True, window_reduce=segment_window(
                "segment", blk.windows[0], 64) is not None)
    needed = sum(counts.spmm_bytes(300, 300, 1700, w, bias=True) for w in (32, 32, 5))
    assert ratio == pytest.approx(moved / needed)
    assert stream_bytes_ratio.read({**rec, "adjacency": None}) is None
