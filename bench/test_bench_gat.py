"""The GAT training and GCN inference cells end to end on the CPU at a cut
size: a sound run is correct; each planted fault and the control fail
the cell's limits; the counts of ``bench.counts_gat`` by hand; the
attention readers on a small record."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, counts, counts_gat, reference_gat
from bench.metrics import attn_kernel_ms, attn_roofline
from bench.modes import infer
from bench.run import cell_spec, parse, run
from bench.traffic import gat as traffic
from bench.traffic import gcn

GAT, INFER = "gat-pubmed.train", "gcn-pubmed.infer"
CUT = dict(n_nodes=300, n_edges=700, n_entries=1700, n_features=40)
SEED = 2**31 + 29
PUBMED = dict(n_nodes=19717, n_entries=108393, n_features=500, n_classes=3,
              heads=8, hidden=8, out_heads=8)


def cut_run(cell, **hooks):
    """One run of ``cell`` at a cut size, with no look for a chip."""
    args = parse(["--workload", cell, "--seed", str(SEED), "--seconds", "0.2",
                  "--trace", "0"])
    return run(args, require_chip=False, resize=CUT, **hooks)


def cut_config(cell):
    return {**cell_spec(cell)["config"], **CUT}


@pytest.mark.parametrize("cell", [GAT, INFER])
def test_sound_run_is_correct(cell):
    """Sound run is correct, and reports the end-to-end metrics."""
    line = cut_run(cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"step_ms", "step_p95_ms", "setup_s"}
    assert list(line)[-1] == "checks"


def test_fault_under_the_timed_path_is_caught():
    """The program's GAT with its coefficient masks left out, put under the
    timed path, makes ``correct`` false."""
    from repro.models.layers import gat_two_layer

    def no_coef_mask(pattern, x, params, *, slope, keeps):
        keeps = {k: v for k, v in keeps.items() if not k.startswith("coef")}
        return gat_two_layer(pattern, x, params, slope=slope, keeps=keeps)

    line = cut_run(GAT, gat=no_coef_mask)
    assert line["correct"] is False


VARIANTS = {"control_written_out": {"dot": reference_gat.dot_bf16},
            **reference_gat.FAULTS}


@pytest.mark.parametrize("name", list(VARIANTS))
def test_gat_faults_and_control_fail(name):
    """Each planted fault, and the control's bfloat16 pass, in the
    program's place fail at least one of the GAT cell's limits."""
    cfg = cut_config(GAT)
    graph = gcn.config_graph(cfg)
    inputs = traffic.make_inputs(cfg, graph, SEED)
    bad = reference_gat.first_steps(cfg, graph, inputs, **VARIANTS[name])
    ref = reference_gat.first_steps(cfg, graph, inputs, follow=bad)
    ok, shown = check.verdict(check.numbers(bad, ref), cell_spec(GAT)["limits"])
    assert not ok, shown


def test_reference_follows_the_run_under_test():
    """Following a run, each reference step starts from that run's
    parameters: following itself it reads nought on every number, and a
    run moved after its first step is compared at the moved points."""
    cfg = cut_config(GAT)
    graph = gcn.config_graph(cfg)
    inputs = traffic.make_inputs(cfg, graph, SEED)
    step = reference_gat.make_train_step(cfg, graph)
    own = reference_gat.first_steps(cfg, graph, inputs, step=step)
    again = reference_gat.first_steps(cfg, graph, inputs, step=step, follow=own)
    assert len(own["params"]) == 4
    assert own["losses"] == again["losses"]
    assert all(v == 0.0 for v in check.numbers(own, again).values())
    for k in own["params_end"]:
        np.testing.assert_array_equal(again["params_end"][k], own["params_end"][k])

    moved = {**own, "params": [own["params"][0]] + [
        {k: v + 0.05 for k, v in p.items()} for p in own["params"][1:]]}
    follow = reference_gat.first_steps(cfg, graph, inputs, step=step, follow=moved)
    apart = reference_gat.first_steps(cfg, graph, inputs, step=step)
    assert follow["losses"][0] == apart["losses"][0]
    assert follow["losses"][1] != apart["losses"][1]
    p1 = {k: jnp.asarray(v, jnp.float32) for k, v in moved["params"][1].items()}
    zeros = jax.tree.map(jnp.zeros_like, p1)
    loss = step(p1, zeros, zeros, jnp.int32(1), inputs["x"], inputs["y"],
                inputs["train"], inputs["dropout_key"])[3]
    assert follow["losses"][1] == float(loss)


@pytest.mark.parametrize("variant", [{"dot": reference_gat.dot_bf16},
                                     {"bias": False}],
                         ids=["control_written_out", "dropped_bias"])
def test_infer_fault_and_control_fail(variant):
    """The dropped biases and the control's bfloat16 pass in the program's
    place fail the inference cell's limit."""
    cfg = cut_config(INFER)
    graph = gcn.config_graph(cfg)
    inputs = infer.make_inputs(cfg, graph, SEED)
    want = infer.forward(cfg, graph, inputs["params"], inputs["x"])
    got = infer.forward(cfg, graph, inputs["params"], inputs["x"], **variant)
    ok, _ = check.verdict({"logits": infer.logit_gap(got, want)},
                          cell_spec(INFER)["limits"])
    assert not ok


def test_reference_masks_are_the_programs():
    """The program's step and the reference draw the same masks: one
    function of the key, the step and the configuration."""
    cfg = cut_config(GAT)
    key = gcn.step_key(gcn.seed_key(SEED), 2)
    a, b = traffic.masks(key, cfg, 1700), traffic.masks(key, cfg, 1700)
    assert set(a) == {"x0", "coef0", "x1", "coef1"}
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        assert set(np.unique(np.asarray(a[k]))) <= {0.0, np.float32(2.5)}
    assert a["coef0"].shape == (1700, cfg["heads"])


def test_attention_counts_by_hand():
    """The first layer's attention at PubMed, by hand."""
    n, nnz = 19717, 108393
    # forward: 7 operations and 8 multiply-adds an entry and head
    assert counts_gat.attn_fwd_flops(nnz, 8, 8) == nnz * 8 * (7 + 16) == 19_944_312
    # indices, s and t, values and output, row max and sum, mask
    assert counts_gat.attn_fwd_bytes(n, nnz, 8, 8) == (
        nnz * 8 + 2 * n * 8 * 4 + 2 * n * 64 * 4 + 2 * n * 8 * 4
        + nnz * 8 * 4) == 16_954_600
    assert counts_gat.attn_bwd_flops(nnz, 8, 8) == nnz * 8 * (12 + 32) == 38_154_336
    # indices, s t and their gradients, V dV dout, m l and the row dot, mask
    assert counts_gat.attn_bwd_bytes(n, nnz, 8, 8) == (
        nnz * 8 + 4 * n * 8 * 4 + 3 * n * 64 * 4 + 3 * n * 8 * 4
        + nnz * 8 * 4) == 23_894_984
    peak = counts.peaks("TPU v5 lite")
    want = sum(max(f / 197e12, b / 819e9) for f, b in (
        (19_944_312, 16_954_600), (38_154_336, 23_894_984),
        (counts_gat.attn_fwd_flops(nnz, 8, 3), counts_gat.attn_fwd_bytes(n, nnz, 8, 3)),
        (counts_gat.attn_bwd_flops(nnz, 8, 3), counts_gat.attn_bwd_bytes(n, nnz, 8, 3))))
    assert counts_gat.attn_least_time_s(PUBMED, peak) == pytest.approx(want, rel=1e-12)


def test_model_flops_by_hand():
    """The GAT step's and the GCN forward's model operations at PubMed."""
    n, nnz = 19717, 108393
    layer0 = (2 * n * 500 * 64 * 2          # x W0 and dW0
              + 2 * 2 * n * 64 * 3          # s, t; their dWh; d a_l, d a_r
              + nnz * 8 * (7 + 16) + nnz * 8 * (12 + 32))
    layer1 = (2 * n * 64 * 24 * 3           # h W1, dW1, dh
              + 2 * 2 * n * 24 * 3
              + nnz * 8 * (7 + 6) + nnz * 8 * (12 + 12))
    assert counts_gat.gat_train_flops(PUBMED) == layer0 + layer1 == 2_816_492_000
    gcn_cfg = dict(n_nodes=n, n_entries=nnz, n_features=500, hidden=16, n_classes=3)
    assert counts_gat.gcn_infer_flops(gcn_cfg) == (
        2 * n * 500 * 16 + 2 * nnz * 16 + 2 * n * 16 * 3 + 2 * nnz * 3) == 321_483_766


HLO = "\n".join(
    f'  %{name} = f32[8]{{0}} custom-call(), custom_call_target="tpu_custom_call", '
    f'metadata={{op_name="jit(step)/{scope}/{kernel}/pallas_call"}}'
    for name, scope, kernel in (
        ("fused_attention_fwd.2", "jvp(gat.layer0)", "fused_attention_fwd"),
        ("fused_attention_fwd.3", "jvp(gat.layer1)", "fused_attention_fwd"),
        ("fused_attention_bwd.2", "transpose(jvp(gat.layer1))", "fused_attention_bwd"),
        ("fused_attention_bwd.3", "transpose(jvp(gat.layer0))", "fused_attention_bwd")))


def test_attention_readers():
    """The readers sum the attention launches' device time a step and
    hold it against the step's least attention time; without a trace, or
    without the four launches, they read nothing."""
    peak = counts.peaks("TPU v5 lite")
    ops = {f"fused_attention_{d}.{i}": 0.002 for d in ("fwd", "bwd") for i in (2, 3)}
    rec = {"trace": {"ops": {**ops, "fusion.1": 0.5}}, "hlo": HLO, "steps": 2,
           "config": PUBMED, "peak": peak}
    assert attn_kernel_ms.read(rec) == pytest.approx(4.0)
    assert attn_roofline.read(rec) == pytest.approx(
        counts_gat.attn_least_time_s(PUBMED, peak) / 0.004 * 100)
    assert attn_kernel_ms.read({**rec, "trace": None}) is None
    assert attn_roofline.read({**rec, "hlo": HLO.splitlines()[0]}) is None
    assert attn_roofline.read({**rec, "config": {"n_nodes": 1}}) is None


def test_kernel_attention_matches_reference_layer():
    """One layer of the program (the fused kernels) against the
    benchmark's own reference layer, masks and all, at a cut size."""
    from repro.models.layers import gat_layer

    cfg = cut_config(GAT)
    graph = gcn.config_graph(cfg)
    rows, cols = jnp.asarray(graph["rows"]), jnp.asarray(graph["indices"])
    p = traffic.init_params(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (cfg["n_nodes"], cfg["n_features"]))
    keeps = traffic.masks(jax.random.PRNGKey(3), cfg, int(rows.shape[0]))
    with jax.default_matmul_precision("highest"):
        got = gat_layer((rows, cols, cfg["n_nodes"]), x, p["w0"], p["al0"],
                        p["ar0"], p["b0"], input_keep=keeps["x0"],
                        coef_keep=keeps["coef0"])
        want = reference_gat.layer(rows, cols, cfg["n_nodes"], x, p["w0"],
                                   p["al0"], p["ar0"], p["b0"], concat=True,
                                   slope=cfg["slope"], input_keep=keeps["x0"],
                                   coef_keep=keeps["coef0"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
