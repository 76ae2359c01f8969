"""Full-batch GCN training, closed loop: one caller steps back to back.

Set-up builds one object, the compiled training step with its state,
and drives it from the seed through its first steps; the measured
window goes on stepping the same object.  Each step is the program's
``gcn_two_layer`` (the fusion planner's two SpMM launches and their
custom VJP) inside ``jax.value_and_grad`` of the masked cross-entropy,
then the program's ``AdamW`` update, in one jitted call that ends in
``block_until_ready``.  The losses, the first gradient as Adam holds
it after step 1, and the parameters after the first step and after the
first steps are compared with the float32 reference
(``bench.reference``) once the window has closed.
"""
from __future__ import annotations

import gc
import shutil
import statistics
import tempfile
import time

import numpy as np

from bench import check, counts, reference, trace
from bench.traffic import gcn as traffic

FIRST_STEPS = 3  # the steps the reference follows
TRACE_SECONDS = 1.0  # the traced window of a ``--trace 1`` run
SLOW = 5.0  # a step this many times the median step is noted as slow
#: the configuration keys this mode runs; besides them a configuration's
#: file holds only documentation (:data:`DOC_KEYS`)
RUN_KEYS = {"n_nodes", "n_edges", "n_entries", "n_features", "n_classes",
            "hidden", "input_dropout", "weight_decay", "lr", "adam_b1",
            "adam_b2", "adam_eps", "train_per_class", "dtype",
            "matmul_precision", "graph_seed", "degree_alpha"}
DOC_KEYS = {"name", "dataset", "source", "paper", "source_model", "reduced",
            "assumed", "departures"}


def check_config(cfg: dict) -> None:
    """Refuse a configuration that states a setting this mode does not
    run: a key it does not know, or a type other than float32."""
    unknown = set(cfg) - RUN_KEYS - DOC_KEYS
    if unknown:
        raise ValueError(f"the train mode does not run {sorted(unknown)}")
    if cfg["dtype"] != "float32":
        raise ValueError(f"the train mode runs float32, not {cfg['dtype']!r}")


def build_program(cfg: dict, graph: dict, *, gcn=None, wrap_step=None) -> dict:
    """The adjacency in the program's CSR, the schedule its selector
    picks, and the jitted training step (not yet compiled).

    ``gcn`` replaces the program's ``gcn_two_layer`` and ``wrap_step``
    wraps the step: the tests plant faults and the control through
    them."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import gcn_two_layer
    from repro.sparse import CSR, Schedule, matrix_stats
    from repro.train.optimizer import AdamW

    check_config(cfg)
    gcn = gcn or gcn_two_layer
    adj = CSR(indptr=jnp.asarray(graph["indptr"]),
              indices=jnp.asarray(graph["indices"]),
              vals=jnp.asarray(graph["vals"]), shape=graph["shape"])
    opt = AdamW(lr=cfg["lr"], b1=cfg["adam_b1"], b2=cfg["adam_b2"],
                eps=cfg["adam_eps"], weight_decay=0.0, clip_norm=None)
    rate, wd = cfg["input_dropout"], cfg["weight_decay"]
    t0 = time.perf_counter()
    # the selector reads the matrix on the host, so it runs before
    # tracing; the one eager forward builds the adjacency's feed formats,
    # which the program memoizes on the matrix
    sched = Schedule.auto(matrix_stats(adj), cfg["hidden"])
    zeros = jnp.zeros((cfg["n_nodes"], cfg["n_features"]), jnp.float32)
    w0 = jnp.zeros((cfg["n_features"], cfg["hidden"]), jnp.float32)
    w1 = jnp.zeros((cfg["hidden"], cfg["n_classes"]), jnp.float32)
    b0, b1 = jnp.zeros((cfg["hidden"],)), jnp.zeros((cfg["n_classes"],))
    jax.block_until_ready(gcn(adj, zeros, w0, w1, b0, b1, schedule=sched))
    format_build_s = time.perf_counter() - t0

    def loss_fn(p, x, y, train, key):
        logits = gcn(adj, traffic.dropout(x, key, rate), p["w0"], p["w1"],
                     p["b0"], p["b1"], schedule=sched)
        logp = jax.nn.log_softmax(logits[train], axis=-1)
        nll = -jnp.mean(jnp.take_along_axis(logp, y[train][:, None], axis=1))
        return nll + 0.5 * wd * jnp.sum(p["w0"] ** 2)

    def step(p, state, x, y, train, key):
        loss, grads = jax.value_and_grad(loss_fn)(
            p, x, y, train, traffic.step_key(key, state.step))
        p, state, _ = opt.update(grads, state, p)
        return p, state, loss

    if wrap_step is not None:
        step = wrap_step(step)
    return {"step": jax.jit(step, donate_argnums=(0, 1)), "opt": opt,
            "schedule": str(sched), "format_build_s": format_build_s}


def compile_step(program: dict, inputs: dict) -> dict:
    """Lower and compile the step for this run's shapes; records the
    compile time and the step's HLO text."""
    state = program["opt"].init(inputs["params"])
    t0 = time.perf_counter()
    compiled = program["step"].lower(inputs["params"], state,
                                     *feed(inputs)).compile()
    return {**program, "compiled": compiled,
            "compile_s": time.perf_counter() - t0, "hlo": compiled.as_text()}


def feed(inputs: dict) -> tuple:
    """The arguments every step takes besides its state."""
    return inputs["x"], inputs["y"], inputs["train"], inputs["dropout_key"]


def first_steps(program: dict, inputs: dict, steps: int = FIRST_STEPS):
    """Drive the compiled step from the run's initial state through its
    first ``steps`` steps: the losses, the first gradient as the
    optimizer holds it after step 1 (``mu / (1 - b1)``) and the
    parameters before, after the first step and at the end, with the live
    state to go on from."""
    import jax

    step, b1 = program["compiled"], program["opt"].b1
    # the step donates its state: the run's initial parameters stay
    # whole for the reference
    params = jax.tree.map(lambda a: a.copy(), inputs["params"])
    snap = {"params0": reference.to_host(params), "losses": []}
    state = program["opt"].init(params)
    args = feed(inputs)
    for t in range(steps):
        params, state, loss = step(params, state, *args)
        snap["losses"].append(float(jax.block_until_ready(loss)))
        if t == 0:
            snap["grad1"] = {k: v / (1.0 - b1)
                             for k, v in reference.to_host(state.mu).items()}
            snap["params1"] = reference.to_host(params)
    snap["params_end"] = reference.to_host(params)
    return snap, (params, state)


def window(program: dict, live: tuple, inputs: dict, seconds: float):
    """Step back to back for ``seconds``; each step ends in
    ``block_until_ready``.  Returns the step times, the window's length
    and the live state."""
    import jax

    step, args = program["compiled"], feed(inputs)
    params, state = live
    times = []
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        params, state, loss = step(params, state, *args)
        jax.block_until_ready(loss)
        t1 = time.perf_counter()
        times.append(t1 - t0)
        if t1 - start >= seconds:
            break
    return times, t1 - start, (params, state, loss)


def slow_steps(times: list) -> dict:
    """The steps over :data:`SLOW` times the median: how many, the time
    they took beyond the median, and the three slowest, each as its start
    in the window (s) and its length (ms)."""
    med = statistics.median(times)
    slow = [i for i, t in enumerate(times) if t > SLOW * med]
    starts = np.concatenate([[0.0], np.cumsum(times)[:-1]])
    worst = sorted(slow, key=lambda i: -times[i])[:3]
    return {"count": len(slow),
            "excess_s": sum(times[i] - med for i in slow),
            "slowest": [[round(float(starts[i]), 3), times[i] * 1e3] for i in worst]}


def p95(values) -> float:
    """The 95th percentile (``statistics.quantiles``, exclusive method)."""
    return statistics.quantiles(values, n=20)[-1]


def run(ctx: dict) -> dict:
    """One run of a training cell; see ``bench/run.py`` for ``ctx``."""
    import jax

    cfg, seed = ctx["config"], ctx["seed"]
    # imports and the TPU runtime's start, then each step of set-up
    laps = {"start": ctx["t_chips"] - ctx["t_start"]}
    t = ctx["t_chips"]

    def lap(name):
        nonlocal t
        laps[name], t = time.perf_counter() - t, time.perf_counter()

    graph = traffic.config_graph(cfg)
    lap("graph")
    # the configuration's float32 is the program's as well: its dense
    # products are compiled at the precision the configuration states
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        program = build_program(cfg, graph, gcn=ctx.get("gcn"),
                                wrap_step=ctx.get("wrap_step"))
        lap("formats")
        inputs = traffic.make_inputs(cfg, graph, seed)
        lap("inputs")
        program = compile_step(program, inputs)
        lap("compile")
    snap, live = first_steps(program, inputs)
    lap("first_steps")
    # what set-up made lives to the end of the run: no collection in the
    # window scans it again
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - ctx["t_start"]

    breakdown = trace_red = None
    if ctx["trace"]:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(logdir)
            with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                times, span_s, last = window(program, live, inputs,
                                             min(ctx["seconds"], TRACE_SECONDS))
            jax.profiler.stop_trace()
            trace_red = trace.reduce_dir(logdir)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        breakdown = trace.breakdown(trace_red)
    else:
        times, span_s, last = window(program, live, inputs, ctx["seconds"])
    gc.unfreeze()

    final_loss = float(last[2])
    device = ctx["device"]
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    del live, last, program["compiled"]

    # the reference runs once the window has closed and the peak is read
    ref = reference.first_steps(cfg, graph, inputs, steps=FIRST_STEPS)
    ok, shown = check.verdict(check.numbers(snap, ref), ctx["limits"])
    attempted = len(times)
    failed = 0 if np.isfinite(final_loss) else attempted

    record = {
        "steps": attempted, "config": cfg, "peak": ctx["peak"],
        "hlo": program["hlo"], "compile_s": program["compile_s"],
        "format_build_s": program["format_build_s"], "trace": trace_red,
        "model_flops": counts.gcn_train_flops(cfg),
    }
    end_to_end = {"step_ms": span_s / attempted * 1e3,
                  "step_p95_ms": p95(times) * 1e3, "setup_s": setup_s}
    return {"correct": bool(ok and failed == 0), "attempted": attempted,
            "failed": failed, "end_to_end": end_to_end, "record": record,
            "memory_peak_bytes": peak, "breakdown": breakdown, "checks": shown,
            "notes": {"schedule": program["schedule"], "losses": snap["losses"],
                      "reference_losses": ref["losses"], "setup_laps_s": laps,
                      "slow_steps": slow_steps(times),
                      "launches_of_no_kernel": [
                          lc["name"] for lc in trace.pallas_launches(program["hlo"])
                          if not lc["kernel"]]}}
