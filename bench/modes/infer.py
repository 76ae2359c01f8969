"""Full-graph GCN inference, closed loop: one caller steps back to back.

Set-up builds the adjacency's formats (the program's ``Schedule.auto``
before tracing, then one eager forward), the run's weights and features
on the device, and the compiled forward; the measured window calls it
back to back, each call ending in ``block_until_ready``.  Each step is
the program's ``gcn_two_layer`` over all nodes in evaluation mode: no
dropout and no gradient, so its two planned ``spmm_eb`` launches and the
dense products are the whole step.  The logits of the first call are
compared with the float32 reference's forward (``bench.reference``'s
dense product at ``HIGHEST`` and ``bench.traffic.gcn``'s propagation)
once the window has closed.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

from bench import check, counts_gat, reference, trace
from bench.modes import train as loop
from bench.traffic import gcn as traffic

#: a bias's spread: biases are drawn, not zero, so that a dropped bias
#: shows in the logits
BIAS_SCALE = 0.1


def make_inputs(cfg: dict, graph: dict, seed: int) -> dict:
    """The training job's features and weights of ``seed``
    (``bench.traffic.gcn.make_inputs``), with biases drawn from the seed."""
    import jax

    inputs = traffic.make_inputs(cfg, graph, seed)
    k0, k1 = jax.random.split(jax.random.fold_in(traffic.seed_key(seed), 1))
    params = dict(inputs["params"])
    params["b0"] = BIAS_SCALE * jax.random.normal(k0, params["b0"].shape)
    params["b1"] = BIAS_SCALE * jax.random.normal(k1, params["b1"].shape)
    return {"x": inputs["x"], "params": params}


def forward(cfg: dict, graph: dict, params: dict, x, *, dot=reference.dot_highest,
            bias: bool = True):
    """The reference's two-layer GCN forward: ``A relu(A (x W0) + b0) W1
    + b1`` by gather, scale and segment sum, dense products by ``dot``;
    ``bias=False`` is the planted fault that drops both biases."""
    import jax.numpy as jnp

    rows, cols, vals = (jnp.asarray(graph[k]) for k in ("rows", "indices", "vals"))
    n = cfg["n_nodes"]
    b0, b1 = (params["b0"], params["b1"]) if bias else (0.0, 0.0)
    z = traffic.propagate(rows, cols, vals, dot(x, params["w0"]), n) + b0
    h = jnp.maximum(z, 0.0)
    return traffic.propagate(rows, cols, vals, dot(h, params["w1"]), n) + b1


def logit_gap(got, want) -> float:
    """The logits' worst gap over the largest reference logit."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def build_program(cfg: dict, graph: dict) -> dict:
    """The adjacency in the program's CSR, the schedule its selector
    picks, and the jitted forward (not yet compiled)."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import gcn_two_layer
    from repro.sparse import CSR, Schedule, matrix_stats

    loop.check_config(cfg)
    adj = CSR(indptr=jnp.asarray(graph["indptr"]),
              indices=jnp.asarray(graph["indices"]),
              vals=jnp.asarray(graph["vals"]), shape=graph["shape"])
    t0 = time.perf_counter()
    sched = Schedule.auto(matrix_stats(adj), cfg["hidden"])
    zeros = jnp.zeros((cfg["n_nodes"], cfg["n_features"]), jnp.float32)
    w0 = jnp.zeros((cfg["n_features"], cfg["hidden"]), jnp.float32)
    w1 = jnp.zeros((cfg["hidden"], cfg["n_classes"]), jnp.float32)
    b0, b1 = jnp.zeros((cfg["hidden"],)), jnp.zeros((cfg["n_classes"],))
    jax.block_until_ready(gcn_two_layer(adj, zeros, w0, w1, b0, b1,
                                        schedule=sched))
    format_build_s = time.perf_counter() - t0

    def step(p, x):
        return gcn_two_layer(adj, x, p["w0"], p["w1"], p["b0"], p["b1"],
                             schedule=sched)

    return {"step": jax.jit(step), "schedule": str(sched),
            "format_build_s": format_build_s}


def window(compiled, inputs: dict, seconds: float):
    """Call the forward back to back for ``seconds``; each call ends in
    ``block_until_ready``.  Returns the step times, the window's length
    and the last logits."""
    import jax

    args = inputs["params"], inputs["x"]
    times = []
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        logits = jax.block_until_ready(compiled(*args))
        t1 = time.perf_counter()
        times.append(t1 - t0)
        if t1 - start >= seconds:
            break
    return times, t1 - start, logits


def run(ctx: dict) -> dict:
    """One run of an inference cell; see ``bench/run.py`` for ``ctx``."""
    import jax

    cfg, seed = ctx["config"], ctx["seed"]
    laps = {"start": ctx["t_chips"] - ctx["t_start"]}
    t = ctx["t_chips"]

    def lap(name):
        nonlocal t
        laps[name], t = time.perf_counter() - t, time.perf_counter()

    graph = traffic.config_graph(cfg)
    lap("graph")
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        program = build_program(cfg, graph)
        lap("formats")
        inputs = make_inputs(cfg, graph, seed)
        lap("inputs")
        t0 = time.perf_counter()
        compiled = program["step"].lower(inputs["params"], inputs["x"]).compile()
        compile_s = time.perf_counter() - t0
        lap("compile")
    first = np.asarray(jax.block_until_ready(compiled(inputs["params"], inputs["x"])))
    for _ in range(loop.FIRST_STEPS - 1):
        jax.block_until_ready(compiled(inputs["params"], inputs["x"]))
    lap("first_steps")
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - ctx["t_start"]

    breakdown = trace_red = None
    if ctx["trace"]:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(logdir)
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                times, span_s, last = window(
                    compiled, inputs, min(ctx["seconds"], loop.TRACE_SECONDS))
            jax.profiler.stop_trace()
            trace_red = trace.reduce_dir(logdir)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        breakdown = trace.breakdown(trace_red)
    else:
        times, span_s, last = window(compiled, inputs, ctx["seconds"])
    gc.unfreeze()

    last = np.asarray(last)
    peak = (ctx["device"].memory_stats() or {}).get("peak_bytes_in_use")
    hlo = compiled.as_text()
    del compiled

    want = forward(cfg, graph, inputs["params"], inputs["x"])
    ok, shown = check.verdict({"logits": logit_gap(first, want)}, ctx["limits"])
    attempted = len(times)
    failed = 0 if np.all(np.isfinite(last)) else attempted
    record = {
        "steps": attempted, "config": cfg, "peak": ctx["peak"], "hlo": hlo,
        "compile_s": compile_s, "format_build_s": program["format_build_s"],
        "trace": trace_red, "model_flops": counts_gat.gcn_infer_flops(cfg),
    }
    end_to_end = {"step_ms": span_s / attempted * 1e3,
                  "step_p95_ms": loop.p95(times) * 1e3, "setup_s": setup_s}
    return {"correct": bool(ok and failed == 0), "attempted": attempted,
            "failed": failed, "end_to_end": end_to_end, "record": record,
            "memory_peak_bytes": peak, "breakdown": breakdown, "checks": shown,
            "notes": {"schedule": program["schedule"], "setup_laps_s": laps,
                      "slow_steps": loop.slow_steps(times),
                      "launches_of_no_kernel": [
                          lc["name"] for lc in trace.pallas_launches(hlo)
                          if not lc["kernel"]]}}
