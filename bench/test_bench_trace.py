"""The reduction of a profiler trace, on a trace recorded on the chip.

``fixtures/gcn-pubmed.train.5-steps.xplane.pb``: five PubMed-size GCN
training steps on one TPU v5e, inside the span ``bench.window`` (with
two host spans around each step that nothing reads).  ``HLO`` holds the
step's lines that the trace's Pallas launches come from, as compiled
for the chip, the kernel bodies cut out.
"""
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "fixtures" / "gcn-pubmed.train.5-steps.xplane.pb"
HLO = """\
  %fusion.88 = f32[19717,16]{1,0} fusion(f32[19717,500]{1,0} %x), kind=kOutput
  %jvp_jit_spmm_eb__.2 = f32[19717,16]{1,0:T(8,128)S(1)} custom-call(%copy-done.29, %copy-done.28, %copy-done.27, %fusion.88, %bitcast.24), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[847,1,128]{2,1,0}, s32[847,1,128]{2,1,0}, f32[847,1,128]{2,1,0}, f32[19717,16]{1,0}, f32[1,16]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step)/jvp(jit(spmm_eb))/pallas_call" stack_frame_id=75}, backend_config={"flag_configs":[],"custom_call_config":{"body":"TUzvUgFNTElSMjIuMC4","needs_layout_passes":true}}
  %jvp_jit_spmm_eb__.3 = f32[19717,8]{1,0:T(8,128)S(1)} custom-call(%copy-done.29, %copy-done.28, %copy-done.27, %pad.0, %pad.2), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[847,1,128]{2,1,0}, s32[847,1,128]{2,1,0}, f32[847,1,128]{2,1,0}, f32[19717,8]{1,0}, f32[1,8]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step)/jvp(jit(spmm_eb))/pallas_call" stack_frame_id=75}, backend_config={"flag_configs":[],"custom_call_config":{"body":"TUzvUgFNTElSMjIuMC4wZ2l0AAFJCwE","needs_layout_passes":true}}
"""
PALLAS = {"jvp_jit_spmm_eb__.2", "jvp_jit_spmm_eb__.3"}
LAUNCHES = trace.pallas_launches(HLO)


@pytest.fixture(scope="module")
def red():
    """The reduction of the recorded trace."""
    return trace.reduce_file(FIXTURE)


def test_pallas_names_from_hlo():
    """Pallas names from hlo."""
    assert [lc["name"] for lc in LAUNCHES] == sorted(PALLAS)
    assert all(lc["kernel"] == "spmm_eb" and not lc["backward"] for lc in LAUNCHES)
    assert trace.op_name("%fusion.8 = f32[3]{0} fusion(%a)") == "fusion.8"


def test_launches_are_matched_by_metadata_not_instruction_name():
    """A launch renamed, as a kernel ``name=`` would rename it, is still
    matched by its wrapper's ``op_name``; a launch under ``transpose(`` is
    a backward one; a launch of another kernel is matched by none, and an
    operand named for an SpMM does not make it one."""
    renamed = HLO.replace("%jvp_jit_spmm_eb__.2 =", "%gcn0_segment.7 =")
    assert [lc["kernel"] for lc in trace.pallas_launches(renamed)] == ["spmm_eb"] * 2
    bwd = ('  %t.4 = f32[9,3]{1,0} custom-call(%jvp_jit_spmm_eb__.2), '
           'custom_call_target="tpu_custom_call", metadata={op_name='
           '"jit(step)/transpose(jvp(jit(spmm_rb)))/pallas_call"}\n')
    other = ('  %k.1 = f32[9,3]{1,0} custom-call(%jvp_jit_spmm_eb__.2), '
             'custom_call_target="tpu_custom_call", metadata={op_name='
             '"jit(step)/jit(sddmm)/pallas_call"}\n')
    got = trace.pallas_launches(HLO + bwd + other)
    assert [(lc["kernel"], lc["backward"]) for lc in got[2:]] == [
        ("spmm_rb", True), (None, False)]


def test_every_pallas_launch_of_the_recorded_trace_is_read(red):
    """Every Pallas launch that ran in the recorded trace is matched to a
    kernel that a metric reads, and every device operation is counted
    once, by the kernels' time or by XLA's."""
    ran = {lc["name"] for lc in LAUNCHES} & set(red["ops"])
    assert ran == PALLAS
    assert all(lc["kernel"] for lc in LAUNCHES if lc["name"] in ran)
    assert trace.spmm_seconds(red, LAUNCHES) + trace.xla_seconds(red, LAUNCHES) == (
        pytest.approx(sum(red["ops"].values())))


def test_window_and_busy(red):
    """Window and busy."""
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.052342859)  # the host span
    assert red["busy_s"] == pytest.approx(0.046357404)  # union of XLA Ops
    assert 0 < red["busy_s"] < red["window_s"]
    # idle: between the five programs, at the window's edges, in a step
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["gaps"].values()) == pytest.approx(idle, rel=1e-9)
    assert red["gaps"]["between steps"] == pytest.approx(0.004730089)
    assert red["gaps"]["in step"] < 1e-4


def test_kernel_and_xla_time(red):
    """Kernel and xla time."""
    # the two eb launches took 2.91 ms each in every one of the 5 steps
    assert trace.spmm_seconds(red, LAUNCHES) == pytest.approx(0.029096478)
    assert red["ops"]["fusion.8"] == pytest.approx(0.005354854)


def test_breakdown(red):
    """Breakdown."""
    bd = trace.breakdown(red)
    assert len(bd["device_ops"]) == trace.TOP
    assert [k for k, _ in bd["device_ops"][:2]] == sorted(PALLAS, reverse=True)
    secs = [v for _, v in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert bd["idle_gaps"][0][0] == "between steps"


def test_metric_readers_on_the_recorded_trace(red):
    """Metric readers on the recorded trace."""
    from bench import counts
    from bench.run import BENCH, cell_spec, load_module

    spec = cell_spec("gcn-pubmed.train")
    rec = {"steps": 5, "trace": red, "hlo": HLO, "config": spec["config"],
           "peak": counts.peaks("TPU v5 lite"), "compile_s": 1.5,
           "format_build_s": 0.5, "model_flops": counts.gcn_train_flops(spec["config"])}
    read = {m["name"]: load_module(BENCH / "metrics" / f"{m['name']}.py").read(rec)
            for m in spec["per_layer"]}
    assert read["launches_per_step"] == 2
    assert read["spmm_kernel_ms"] == pytest.approx(5.8192956)
    assert read["spmm_roofline"] == pytest.approx(4_889_092 / 819e9 / 5.8192956e-3 * 100)
    assert 0.05 < read["spmm_roofline"] < 0.2
    assert read["idle_share"] == pytest.approx((1 - 0.046357404 / 0.052342859) * 100)
    assert 0.01 < read["step_mfu"] < 0.1
    assert read["xla_ms"] == pytest.approx((0.046357404 - 0.029096478) / 5 * 1e3)
    assert read["compile_s"] == 1.5 and read["format_build_s"] == 0.5


def test_readers_find_nothing_without_a_trace():
    """Readers find nothing without a trace."""
    from bench.run import BENCH, load_module

    rec = {"steps": 5, "trace": None, "hlo": HLO}
    for name in ("step_mfu", "xla_ms", "spmm_kernel_ms", "spmm_roofline", "idle_share"):
        assert load_module(BENCH / "metrics" / f"{name}.py").read(rec) is None
