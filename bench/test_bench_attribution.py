"""Attribution of a traced window to program scopes and host phases.

Two recorded chip traces of five PubMed-size training steps on one TPU
v5e: ``fixtures/gcn-pubmed.train.5-steps.xplane.pb``, of a program that
named no scope, and ``fixtures/gcn-pubmed.train.5-steps.scoped.*``, of
the program with its scopes and kernel names, with the compiled step's
HLO lines of the operations that ran (``bench/record_trace.py --steps
5 --out``, renamed).  Both host planes hold the runtime's own events,
which the host phases read.
"""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import attribution, trace

FIXTURES = Path(__file__).parent / "fixtures"
UNSCOPED_TRACE = FIXTURES / "gcn-pubmed.train.5-steps.xplane.pb"
SCOPED_TRACE = FIXTURES / "gcn-pubmed.train.5-steps.scoped.xplane.pb"
SCOPED_HLO = FIXTURES / "gcn-pubmed.train.5-steps.scoped.hlo.txt"


def _profile(path):
    from jax.profiler import ProfileData

    return ProfileData.from_file(str(path))


@pytest.fixture(scope="module")
def unscoped():
    """The older fixture's profile and reduction."""
    prof = _profile(UNSCOPED_TRACE)
    return prof, trace.reduce(prof)


@pytest.fixture(scope="module")
def scoped():
    """The scoped fixture's profile, reduction and HLO lines."""
    prof = _profile(SCOPED_TRACE)
    return prof, trace.reduce(prof), SCOPED_HLO.read_text()


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/transpose(jvp(fuse.launch0.spmm))/spmm.bwd/act/jvp()/div", "spmm.bwd.act"),
    ("jit(step)/transpose(jvp(fuse.launch1.spmm))/spmm.bwd/tspmm/scatter-add",
     "spmm.bwd.tspmm"),
    ("jit(step)/transpose(jvp(fuse.launch1.spmm))/spmm.bwd/convert_element_type", "spmm.bwd"),
    ("jit(step)/transpose(jvp(fuse.launch0.spmm))/transpose", "fuse.launch0.spmm"),
    ("jit(step)/jvp(fuse.launch12.grouped_matmul)/dot_general", "fuse.launch12.grouped_matmul"),
    ("jit(step)/optimizer/sqrt", "optimizer"),
    ("jit(step)/jvp()/mul", "unscoped"),
    ("jit(step)/jvp(jit(spmm_eb))/pallas_call", "unscoped"),
    ("jit(step)/spmm.bwd/actual/add", "spmm.bwd"),
    ("jit(step)/my_optimizer/add", "unscoped"),
    ("jit(step)/optimizer/mul;jit(step)/transpose(jvp())/mul", "optimizer"),
])
def test_scope_of_op_name(op_name, scope):
    """The innermost named scope of an ``op_name``, by whole names."""
    assert attribution.scope_of(op_name) == scope


def test_scope_buckets_and_pallas_time_sum_to_the_ops():
    """Every operation of a synthetic reduction is counted once: by the
    Pallas launches, or in one scope's bucket; unknown names are
    unscoped."""
    hlo = "\n".join([
        '  %spmm_eb.2 = f32[9,3]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/jvp(fuse.launch0.spmm)/jit(spmm_eb)/spmm_eb/pallas_call"}',
        '  %fusion.8 = f32[9,3]{1,0} fusion(%b), kind=kLoop, calls=%f.1, metadata={op_name='
        '"jit(step)/transpose(jvp(fuse.launch0.spmm))/spmm.bwd/tspmm/scatter-add"}',
        '  %fusion.7 = f32[9,3]{1,0} fusion(%b), kind=kLoop, calls=%f.2, metadata={op_name='
        '"jit(step)/transpose(jvp(fuse.launch0.spmm))/spmm.bwd/recompute/mul"}',
        '  %add.3 = f32[3]{0} add(%c, %d), metadata={op_name="jit(step)/optimizer/add"}',
        '  ROOT %tuple.9 = (f32[3]{0}) tuple(%add.3), metadata={op_name="jit(step)/jvp()/mul"}',
    ])
    red = {"ops": {"spmm_eb.2": 3.0, "fusion.8": 1.5, "fusion.7": 0.25, "add.3": 0.125,
                   "tuple.9": 0.0625, "copy-start": 0.03125}}
    launches = trace.pallas_launches(hlo)
    by_scope = attribution.xla_by_scope(red, hlo, launches)
    assert by_scope == {"spmm.bwd.tspmm": 1.5, "spmm.bwd.recompute": 0.25, "optimizer": 0.125,
                        "unscoped": 0.0625 + 0.03125}
    assert sum(by_scope.values()) == trace.xla_seconds(red, launches)
    assert sum(by_scope.values()) + trace.spmm_seconds(red, launches) == sum(red["ops"].values())
    assert attribution.spmm_bwd_seconds(by_scope) == 1.75


def test_host_phases_of_the_recorded_trace(unscoped):
    """The host's dispatch and completion a step, the causal clock
    bracket, and the between-steps idle time split into phases that sum
    to it, on the recorded trace."""
    prof, red = unscoped
    got = attribution.host_phases(prof)
    # call start to the execute call's return: 0.373, 0.342, 0.241,
    # 0.224, 0.262 ms; the flag's read to the completion's end: 0.418,
    # 0.277, 0.321, 0.362, 0.280 ms
    assert got["dispatch_s"] == pytest.approx(0.2881292e-3)
    assert got["completion_s"] == pytest.approx(0.3314018e-3)
    # the device's programs start 0.99-1.09 ms before their launch
    # begins on the host's clock, and end 1.86-1.96 ms before the host's
    # completion read ends: the offset lies in [1.0871, 1.8607] ms
    assert got["clock_offset_s"] == pytest.approx(1.08714e-3)
    assert got["clock_bracket_s"] == pytest.approx(0.773577e-3)
    assert got["between_steps_s"] == pytest.approx(red["gaps"]["between steps"], rel=1e-12)
    phases = got["gap_phases_s"]
    assert list(phases) == list(attribution.PHASES)
    assert all(v >= 0.0 for v in phases.values())
    assert sum(phases.values()) == pytest.approx(got["between_steps_s"], rel=1e-12)
    # per gap (4): 0.595 ms to the read, 0.344 completing, 0.038 in
    # Python, 0.048 handling arguments, 0.157 in the execute call until
    # the device starts
    assert phases["until_read"] == pytest.approx(2.380643e-3)
    assert phases["completion"] == pytest.approx(1.377359e-3)
    assert phases["python"] == pytest.approx(0.153774e-3)
    assert phases["dispatch"] == pytest.approx(0.192275e-3)
    assert phases["execute"] == pytest.approx(0.626038e-3)


def _fake_profile(host_events, modules, ops):
    """A ``ProfileData`` look-alike: one host line, one device plane."""
    ev = lambda name, s, e: SimpleNamespace(  # noqa: E731
        name=name, start_ns=s, end_ns=e, duration_ns=e - s)
    line = lambda name, evs: SimpleNamespace(  # noqa: E731
        name=name, events=[ev(*x) for x in evs])
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[line("main", host_events)]),
        SimpleNamespace(name="/device:TPU:0", lines=[
            line(trace.MODULES_LINE, [("jit_step", *m) for m in modules]),
            line(trace.OPS_LINE, [("%op.1 = f32[] add()", *o) for o in ops])])])


def _step_events(t0):
    """The runtime's events of one step whose call starts at ``t0``."""
    ev = attribution.EVENTS
    return [(ev["call"] + "jit(step))", t0, t0 + 400), (ev["execute"], t0 + 100, t0 + 380),
            (ev["launch"], t0 + 200, t0 + 300), (ev["read"], t0 + 5000, t0 + 5200),
            (ev["done"], t0 + 5300, t0 + 5400)]


def test_host_phases_are_none_where_events_are_missing():
    """The phases of a synthetic gap; without the runtime's events, or
    with calls and programs that do not pair, they read None: they are
    never guessed."""
    modules = ops = [(1200, 5000), (11200, 15000)]
    whole = _step_events(1000) + _step_events(11000)
    got = attribution.host_phases(_fake_profile(whole, modules, ops))
    # programs start as their launches begin; reads end 1200 ns after
    assert got["clock_offset_s"] == 0.0
    assert got["clock_bracket_s"] == pytest.approx(1200e-9)
    # the execute call ends after the device started: cut there
    assert got["gap_phases_s"] == pytest.approx(dict(zip(
        attribution.PHASES, [1000e-9, 400e-9, 4600e-9, 100e-9, 100e-9, 0.0])))
    assert got["between_steps_s"] == pytest.approx(6200e-9)
    no_read = [e for e in whole if e[0] != attribution.EVENTS["read"]]
    assert set(attribution.host_phases(_fake_profile(no_read, modules, ops)).values()) == {None}
    one_program = attribution.host_phases(_fake_profile(whole, modules[:1], ops[:1]))
    assert one_program["dispatch_s"] == pytest.approx(380e-9)
    assert one_program["clock_offset_s"] is None and one_program["gap_phases_s"] is None


def test_spmm_bwd_reader_finds_nothing_in_an_unscoped_program(unscoped):
    """``spmm_bwd_ms`` reads None on a program that names no scope, as
    the older fixture's, and without a trace."""
    from bench.run import BENCH, load_module
    from bench.test_bench_trace import HLO

    reader = load_module(BENCH / "metrics" / "spmm_bwd_ms.py")
    _, red = unscoped
    assert reader.read({"steps": 5, "trace": red, "hlo": HLO}) is None
    assert reader.read({"steps": 5, "trace": None, "hlo": HLO}) is None


def test_the_bench_step_names_its_layers():
    """The training cell's step, compiled at a cut size, carries the
    program's scopes in its instructions' ``op_name``."""
    import json

    import jax

    from bench.modes import train
    from bench.run import ROOT
    from bench.traffic import gcn as traffic

    cfg = json.loads((ROOT / "bench" / "configs" / "gcn-cora.json").read_text())
    cfg.update(n_nodes=300, n_edges=700, n_entries=1700, n_features=40)
    graph = traffic.config_graph(cfg)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        program = train.build_program(cfg, graph)
        program = train.compile_step(program, traffic.make_inputs(cfg, graph, 2**32 + 3))
    scopes = set(attribution.instruction_scopes(program["hlo"]).values())
    assert {"fuse.launch0.spmm", "spmm.bwd.recompute", "spmm.bwd.tspmm",
            "optimizer"} <= scopes
    # the first launch's backward (its ReLU) holds the recompute
    assert "fuse.launch0.spmm/spmm.bwd/recompute" in program["hlo"].replace(")", "")


def test_scoped_trace_splits_the_backward(scoped):
    """On the chip's trace of the scoped program: the two launches are
    named ``spmm_eb``, the scope buckets sum to the XLA time, and the
    SpMM backward is most of it, its three ~1 ms fusions the recompute
    and the two transpose SpMMs."""
    _, red, hlo = scoped
    launches = trace.pallas_launches(hlo)
    assert [(lc["name"], lc["kernel"], lc["backward"]) for lc in launches] == [
        ("spmm_eb.2", "spmm_eb", False), ("spmm_eb.3", "spmm_eb", False)]
    by_scope = attribution.xla_by_scope(red, hlo, launches)
    assert sum(by_scope.values()) == pytest.approx(trace.xla_seconds(red, launches), rel=1e-12)
    assert set(by_scope) == {"fuse.launch0.spmm", "fuse.launch1.spmm", "spmm.bwd.recompute",
                             "spmm.bwd.act", "spmm.bwd.tspmm", "spmm.bwd.dbias", "optimizer",
                             "unscoped"}
    scopes = attribution.instruction_scopes(hlo)
    assert [scopes[f] for f in ("fusion.8", "fusion.7", "fusion.9")] == [
        "spmm.bwd.recompute", "spmm.bwd.tspmm", "spmm.bwd.tspmm"]
    assert by_scope["spmm.bwd.recompute"] == pytest.approx(0.005408207)
    assert by_scope["spmm.bwd.tspmm"] == pytest.approx(0.009610748)
    bwd = attribution.spmm_bwd_seconds(by_scope)
    assert bwd == pytest.approx(0.015075506)
    assert bwd > 0.8 * trace.xla_seconds(red, launches)


def test_metric_readers_on_the_scoped_trace(scoped):
    """Every per-layer reader of the PubMed cell reads the scoped trace;
    ``spmm_bwd_ms`` reads the backward's device time a step."""
    from bench import counts
    from bench.run import cell_spec, load_module

    _, red, hlo = scoped
    spec = cell_spec("gcn-pubmed.train")
    rec = {"steps": 5, "trace": red, "hlo": hlo, "config": spec["config"],
           "peak": counts.peaks("TPU v5 lite"), "compile_s": 0.5,
           "format_build_s": 0.7, "model_flops": counts.gcn_train_flops(spec["config"])}
    read = {m["name"]: load_module(m["reader"]).read(rec) for m in spec["per_layer"]}
    assert None not in read.values()
    assert read["launches_per_step"] == 2
    assert read["spmm_bwd_ms"] == pytest.approx(3.0151012)
    assert read["spmm_kernel_ms"] == pytest.approx(5.8192978)
    assert read["xla_ms"] == pytest.approx(3.452056)


def test_host_phases_of_the_scoped_trace(scoped):
    """The scoped trace's host phases: dispatch and completion a step,
    and phases that sum to the between-steps idle time."""
    prof, red, _ = scoped
    got = attribution.host_phases(prof)
    assert got["dispatch_s"] == pytest.approx(0.3382838e-3)
    assert got["completion_s"] == pytest.approx(0.3243542e-3)
    assert got["clock_offset_s"] == pytest.approx(0.99797e-3)
    assert got["clock_bracket_s"] == pytest.approx(0.75438e-3)
    assert got["between_steps_s"] == pytest.approx(red["gaps"]["between steps"], rel=1e-12)
    assert sum(got["gap_phases_s"].values()) == pytest.approx(got["between_steps_s"], rel=1e-12)
