"""Low-precision sparse kernels (ISSUE 9, DESIGN.md §13).

Covers the acceptance surface: bf16/fp16/fp8/int8 forward + gradient
parity against the f32 oracle across reduction strategies (per-dtype
tolerances, compared against the *same-strategy* f32 output so a lossy
strategy is not misattributed to the dtype), quantize/dequantize
round-trips and calibration, empty-row / single-nnz / empty-matrix
edges, dtype-preservation regressions in the format constructors,
dtype-axis tuning with zero-remeasure cache replay, the v3 -> v4 cache
schema migration, the fp8 -> bf16 degradation path, and the roofline
byte accounting validated against XLA's compiled memory analysis.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Schedule, cost_terms
from repro.core.dtypes import (
    Fp8Fallback,
    canonical_value_dtype,
    fp8_supported,
    operand_dtype,
    operand_itemsize,
    storage_dtype,
    value_itemsize,
)
from repro.kernels import ref
from repro.sparse import (
    CSR,
    QuantizedCSR,
    dequantize,
    matrix_stats,
    quantize_csr,
    random_csr,
    spmm,
)
from repro.tune import SCHEMA_VERSION, ScheduleCache, TuneRecord, tune_schedule
from repro.tune.search import schedule_key

#: relative-L2 forward tolerance per storage dtype (storage rounding
#: only — accumulation is f32 everywhere, the upcast_f32 contract)
TOL = {"bfloat16": 2e-2, "float16": 3e-3, "float8_e4m3fn": 1.5e-1,
       "int8": 5e-2}

SCHEDULES = [
    Schedule("eb", nnz_tile=128, group_size=8, strategy="segment"),
    Schedule("eb", nnz_tile=128, group_size=8, strategy="accumulate"),
    Schedule("eb", nnz_tile=128, group_size=16, strategy="parallel"),
    Schedule("rb", row_tile=8, strategy="parallel"),
]


def _rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-12))


def _mat(n=96, density=0.06, seed=0):
    return random_csr(n, n, density=density, seed=seed)


def _b(csr, C=16, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (csr.shape[1], C))


# ---------------------------------------------------------------------------
# Schedule axis validation + keys
# ---------------------------------------------------------------------------


def test_canonical_value_dtype():
    assert canonical_value_dtype(None) is None
    assert canonical_value_dtype("float32") is None  # axis identity
    assert canonical_value_dtype("f32") is None
    assert canonical_value_dtype("bf16") == "bfloat16"
    assert canonical_value_dtype(jnp.bfloat16) == "bfloat16"
    assert canonical_value_dtype("fp8") == "float8_e4m3fn"
    assert canonical_value_dtype("int8") == "int8"
    with pytest.raises(ValueError):
        canonical_value_dtype("int4")


def test_schedule_validates_and_normalizes_value_dtype():
    s = Schedule("eb", value_dtype="bf16")
    assert s.value_dtype == "bfloat16"
    assert Schedule("eb", value_dtype="float32").value_dtype is None
    with pytest.raises(ValueError):
        Schedule("eb", value_dtype="float64")


def test_schedule_key_dtype_suffix():
    base = Schedule("eb", nnz_tile=128, group_size=8, strategy="segment")
    k0 = schedule_key(base)
    assert ":v[" not in k0  # pre-dtype-axis keys unchanged
    k1 = schedule_key(base.replace(value_dtype="bfloat16"))
    assert k1 == k0.replace(":segment", ":segment:v[bfloat16]")
    # replace() round-trips through validation
    assert base.replace(value_dtype="bf16").value_dtype == "bfloat16"


def test_itemsizes():
    assert value_itemsize(None) == 4
    assert value_itemsize("bfloat16") == 2
    assert value_itemsize("int8") == 1
    assert operand_itemsize("int8") == 2  # int8 pairs with a bf16 operand
    assert operand_dtype("int8") == jnp.bfloat16


# ---------------------------------------------------------------------------
# Forward + gradient parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sched", SCHEDULES, ids=lambda s: schedule_key(s))
@pytest.mark.parametrize("vd", ["bfloat16", "float16", "int8"])
def test_forward_parity_vs_same_strategy_f32(sched, vd):
    """Narrow output compared against the *same schedule* at f32 — the
    dtype axis must only add storage rounding, whatever the strategy's
    own deviation from the oracle is."""
    csr = _mat()
    b = _b(csr)
    out32 = spmm(csr, b, sched)
    outn = spmm(csr, b, sched.replace(value_dtype=vd))
    assert outn.dtype == jnp.float32  # accumulation/output stay f32
    assert _rel(outn, out32) < TOL[vd]


def test_forward_parity_vs_oracle():
    """Sanity anchor: with a deviation-free strategy the narrow outputs
    are also close to the dense oracle, not just to each other."""
    csr = _mat()
    b = _b(csr)
    oracle = np.asarray(csr.todense(), np.float64) @ np.asarray(b, np.float64)
    sched = SCHEDULES[0]
    for vd in ("bfloat16", "float16", "int8"):
        out = spmm(csr, b, sched.replace(value_dtype=vd))
        assert _rel(out, oracle) < TOL[vd]


def test_gradients_narrow_float():
    """Narrow-float CSR spmm stays differentiable in all args; grads are
    the straight-through f32 grads up to storage rounding."""
    csr = _mat(64, 0.08)
    b = _b(csr, 8)
    sched = SCHEDULES[0]

    def loss(bb, s):
        return jnp.sum(spmm(csr, bb, s) ** 2)

    g32 = jax.grad(loss)(b, sched)
    gbf = jax.grad(loss)(b, sched.replace(value_dtype="bfloat16"))
    assert _rel(gbf, g32) < 5e-2


def test_gradients_int8_quantized():
    """int8 path differentiates through b (vals are host-side codes)."""
    csr = _mat(64, 0.08)
    b = _b(csr, 8)
    sched = SCHEDULES[0]

    def loss(bb):
        return jnp.sum(spmm(csr, bb, sched.replace(value_dtype="int8")) ** 2)

    gq = jax.grad(loss)(b)
    g32 = jax.grad(lambda bb: jnp.sum(spmm(csr, bb, sched) ** 2))(b)
    assert _rel(gq, g32) < 5e-2


def test_quantized_csr_direct_input():
    """A pre-quantized operand dispatches the quantized kernels under
    'auto' scheduling and matches its own dequantized reference."""
    csr = _mat()
    b = _b(csr)
    q = csr.quantized()
    out = spmm(q, b, "auto")
    want = ref.spmm_coo_ref(q.csr.tocoo().rows, q.csr.tocoo().cols,
                            q.dequantize().tocoo().vals, b, csr.shape[0])
    assert _rel(out, want) < 2e-2


# ---------------------------------------------------------------------------
# Quantization
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_per_row():
    csr = _mat()
    q = quantize_csr(csr)
    assert q.csr.vals.dtype == jnp.int8
    assert q.scales.shape == (csr.shape[0],)
    deq = dequantize(q)
    # per-element error bounded by scale/2 per row
    vals = np.asarray(csr.vals)
    rows = np.repeat(np.arange(csr.shape[0]),
                     np.diff(np.asarray(csr.indptr)))
    err = np.abs(np.asarray(deq.vals) - vals)
    assert np.all(err <= np.asarray(q.scales)[rows] / 2 + 1e-7)


def test_quantize_empty_rows_and_methods():
    # matrix with empty rows: their scale must be the harmless 1.0
    indptr = np.array([0, 2, 2, 3], np.int32)
    indices = np.array([0, 2, 1], np.int32)
    vals = np.array([1.0, -3.0, 0.5], np.float32)
    csr = CSR(indptr, indices, vals, (3, 3))
    q = quantize_csr(csr)
    assert float(q.scales[1]) == 1.0
    # percentile calibration clips outliers before the absmax
    qp = quantize_csr(csr, method="percentile", percentile=50.0)
    assert float(qp.scales[0]) <= float(q.scales[0])
    with pytest.raises(ValueError):
        quantize_csr(csr, method="bogus")


def test_quantized_memoization():
    csr = _mat()
    assert csr.quantized() is csr.quantized()
    assert csr.astype(jnp.float32) is csr
    assert csr.astype(jnp.bfloat16) is csr.astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# Edges + dtype-preservation regressions
# ---------------------------------------------------------------------------


def test_single_nnz_and_empty_matrix():
    indptr = np.array([0, 1, 1], np.int32)
    csr = CSR(indptr, np.array([0], np.int32),
              np.array([2.5], np.float32), (2, 2))
    b = jnp.ones((2, 4))
    sched = SCHEDULES[0]
    for vd in ("bfloat16", "int8"):
        out = spmm(csr, b, sched.replace(value_dtype=vd))
        assert _rel(out, [[2.5] * 4, [0.0] * 4]) < TOL[vd]
    empty = CSR(np.zeros(3, np.int32), np.zeros(0, np.int32),
                np.zeros(0, np.float32), (2, 2))
    q = quantize_csr(empty)
    assert q.csr.nnz == 0 and np.all(np.asarray(q.scales) == 1.0)


def test_ell_preserves_value_dtype_when_empty():
    """Regression: ELL.fromcsr used to silently widen an *empty* narrow
    value stream back to f32."""
    empty = CSR(np.zeros(3, np.int32), np.zeros(0, np.int32),
                np.zeros(0, np.float32), (2, 2))
    bf = empty.astype(jnp.bfloat16)
    assert bf.ell(row_tile=8).vals.dtype == jnp.bfloat16


def test_grouped_padding_preserves_value_dtype():
    csr = _mat(48, 0.1)
    bf = csr.astype(jnp.bfloat16)
    g = bf.grouped(64, group_size=8)
    assert g.vals.dtype == jnp.bfloat16
    assert bf.ell(row_tile=8).vals.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# fp8 fallback
# ---------------------------------------------------------------------------


def test_fp8_degrades_to_bf16_with_warning(monkeypatch):
    monkeypatch.setenv("REPRO_DISABLE_FP8", "1")
    assert not fp8_supported()
    with pytest.warns(Fp8Fallback):
        assert storage_dtype("float8_e4m3fn") == jnp.bfloat16
    assert value_itemsize("float8_e4m3fn") == 2  # realized width
    # end-to-end: the degraded schedule runs and equals its bf16 twin
    csr = _mat(64, 0.08)
    b = _b(csr, 8)
    sched = SCHEDULES[0]
    with pytest.warns(Fp8Fallback):
        out8 = spmm(csr, b, sched.replace(value_dtype="fp8"))
    outbf = spmm(csr, b, sched.replace(value_dtype="bfloat16"))
    assert _rel(out8, outbf) == 0.0


def test_fp8_native_when_available(monkeypatch):
    monkeypatch.delenv("REPRO_DISABLE_FP8", raising=False)
    assert fp8_supported()
    assert storage_dtype("fp8") == jnp.float8_e4m3fn
    assert value_itemsize("fp8") == 1
    csr = _mat(64, 0.08)
    b = _b(csr, 8)
    out = spmm(csr, b, SCHEDULES[0].replace(value_dtype="fp8"))
    oracle = np.asarray(csr.todense(), np.float64) @ np.asarray(
        b, np.float64)
    assert _rel(out, oracle) < TOL["float8_e4m3fn"]


# ---------------------------------------------------------------------------
# Tuning: dtype as a searched axis, cache replay, schema migration
# ---------------------------------------------------------------------------


def _counting_measure(bias_dtype=None):
    calls = {"n": 0}

    def measure(s):
        calls["n"] += 1
        # make the biased dtype strictly fastest so the tuner must pick it
        return 0.5e-6 if s.value_dtype == bias_dtype else 1e-6

    return measure, calls


def test_tuner_picks_dtype_and_replays_with_zero_measurements(tmp_path):
    csr = _mat()
    cache = ScheduleCache(path=str(tmp_path / "c.json"))
    measure, calls = _counting_measure("bfloat16")
    res = tune_schedule(csr, 16, cache=cache, measure=measure,
                        value_dtypes=("bfloat16",))
    assert res.schedule.value_dtype == "bfloat16"
    assert not res.from_cache and calls["n"] > 0
    n_first = calls["n"]
    replay = tune_schedule(csr, 16, cache=cache, measure=measure,
                           value_dtypes=("bfloat16",))
    assert replay.from_cache and replay.n_measurements == 0
    assert calls["n"] == n_first  # zero re-measurements
    assert replay.schedule.value_dtype == "bfloat16"
    # the record survives a from-disk reload with its dtype intact
    fresh = ScheduleCache(path=str(tmp_path / "c.json"))
    rec = fresh.get(res.key)
    assert rec is not None and rec.schedule.value_dtype == "bfloat16"


def test_tuner_error_budget_gates_dtypes(tmp_path):
    csr = _mat()
    measure, _ = _counting_measure("bfloat16")
    res = tune_schedule(csr, 16, cache=ScheduleCache(path=None),
                        measure=measure, error_budget=0.0)
    assert res.schedule.value_dtype is None  # nothing fits a 0% budget
    res = tune_schedule(csr, 16, cache=ScheduleCache(path=None),
                        measure=measure, value_dtypes=())
    assert res.schedule.value_dtype is None  # axis disabled


def test_cache_v3_records_are_dropped(tmp_path):
    """v3 -> v4 migration: pre-dtype-axis records must not replay (they
    would silently pin f32 storage); the version gate drops the file
    wholesale and the workload re-tunes."""
    path = tmp_path / "cache.json"
    cache = ScheduleCache(path=str(path))
    cache.put("k", TuneRecord(schedule=Schedule("eb"), us_per_call=1.0))
    cache.save()
    raw = json.loads(path.read_text())
    assert raw["version"] == SCHEMA_VERSION == 4
    raw["version"] = 3
    path.write_text(json.dumps(raw))
    stale = ScheduleCache(path=str(path))
    assert stale.get("k") is None and len(stale) == 0


def test_cost_terms_scale_with_dtype():
    csr = _mat()
    stats = matrix_stats(csr)
    s = Schedule("eb", nnz_tile=128, group_size=8, strategy="segment")
    work, waste, wb, gather = cost_terms(stats, s, 16)
    w2, waste2, wb2, g2 = cost_terms(
        stats, s.replace(value_dtype="bfloat16"), 16)
    assert (w2, wb2) == (work, wb)  # compute/writeback stay f32
    assert g2 == pytest.approx(gather / 2)
    assert waste2 == pytest.approx(waste / 2)
    *_, g1 = cost_terms(stats, s.replace(value_dtype="int8"), 16)
    assert g1 == pytest.approx(gather / 2)  # int8 pairs with bf16 operand


def test_serve_prepare_sparse_can_pin_f32(monkeypatch):
    """``value_dtypes=()`` must reach tune_schedule and disable the
    axis (a parity-critical serving path pins f32 storage)."""
    from repro.serve import engine as serve_engine
    from repro.serve.engine import ServeEngine

    class _API:
        def init_cache(self, slots, max_len):
            return {}

        def decode_step(self, params, cache, toks):  # pragma: no cover
            raise NotImplementedError

    eng = ServeEngine(_API(), params={}, slots=1,
                      tuner_cache=ScheduleCache(path=None))
    csr = _mat()
    seen = {}

    import repro.tune as tune_mod

    real = tune_mod.tune_schedule

    def spy(c, n, **kw):
        seen.update(kw)
        measure, _ = _counting_measure()
        return real(c, n, measure=measure, **kw)

    monkeypatch.setattr(tune_mod, "tune_schedule", spy)
    sched = eng.prepare_sparse(csr, 16, value_dtypes=(),
                               error_budget=0.01)
    assert seen.get("value_dtypes") == ()
    assert seen.get("error_budget") == 0.01
    assert sched.value_dtype is None


# ---------------------------------------------------------------------------
# Roofline byte accounting vs compiled reality
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("vd", [None, "bfloat16"])
def test_predicted_arg_bytes_match_compiled(vd):
    """The byte model the bench reports is the number XLA's memory
    analysis measures on the compiled tuner runner (PR 8 style)."""
    from repro.roofline.analysis import predict_spmm_arg_bytes
    from repro.tune.measure import make_eb_runner

    csr = _mat()
    fn, args = make_eb_runner(csr, 16, group_size=8, strategy="accumulate",
                              value_dtype=vd)
    try:
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
    except Exception:
        pytest.skip("memory_analysis unavailable on this jax")
    if ma is None:
        pytest.skip("memory_analysis unavailable on this jax")
    pred = predict_spmm_arg_bytes(args[0].shape[0], csr.shape[1], 16,
                                  value_dtype=vd)
    assert ma.argument_size_in_bytes == pred


def test_predicted_traffic_scales_down():
    from repro.roofline.analysis import (
        dtype_itemsize,
        predict_spmm_traffic_bytes,
    )

    assert dtype_itemsize("bf16") == 2
    assert dtype_itemsize("f8e4m3fn") == 1
    assert dtype_itemsize(np.float32) == 4
    b32 = predict_spmm_traffic_bytes(10_000, 512, 64)
    bbf = predict_spmm_traffic_bytes(10_000, 512, 64,
                                     value_dtype="bfloat16")
    assert 1.5 < b32 / bbf < 2.0  # gather dominated -> near-2x


# ---------------------------------------------------------------------------
# launch.backend
# ---------------------------------------------------------------------------


def test_backend_info_and_interpret_default():
    from repro.launch import backend

    info = backend.backend_info()
    assert set(info) == {"backend", "device_kind", "device_count", "fp8",
                         "interpret"}
    assert info["device_count"] >= 1
    # CPU (this container) always interprets Pallas
    if info["backend"] == "cpu":
        assert info["interpret"] is True


def test_set_host_device_count_appends_flag(monkeypatch):
    from repro.launch import backend

    monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_foo=1")
    backend.set_host_device_count(4)
    import os

    flags = os.environ["XLA_FLAGS"]
    assert "--xla_cpu_foo=1" in flags
    assert "--xla_force_host_platform_device_count=4" in flags
    backend.set_host_device_count(8)  # replaces, never duplicates
    flags = os.environ["XLA_FLAGS"]
    assert flags.count("--xla_force_host_platform_device_count") == 1
    assert "--xla_force_host_platform_device_count=8" in flags
    with pytest.raises(ValueError):
        backend.set_host_device_count(0)
