"""Full-batch training of OGB's L-layer GCN with a batch norm between
layers, closed loop: one caller steps back to back.

Set-up builds the compiled training step and drives it from the seed
through its first steps; the measured window goes on stepping the same
object.  Each step is the program's ``gcn`` (``models.layers``: the
fusion planner's one SpMM launch a layer, each bias in its epilogue,
and between the launches the batch norm, ReLU and dropout in XLA; the
SpMMs' custom VJP) inside ``jax.value_and_grad`` of the mean negative
log-likelihood over the training nodes, then the program's ``AdamW``
update, in one jitted call that ends in ``block_until_ready``.  The step
carries the norms' running statistics with the optimizer's state.  The
dropout masks come from the run's key and the step count
(``bench.traffic.gcn_deep.masks``).  The losses, the first gradient and
the parameters after the first step and after the first steps are
compared with the float32 reference (``bench.reference_gcn_deep``),
which takes each step from the program's parameters before it, once
the window has closed.  The window and its statistics are the GCN
training mode's (``bench.modes.train``).
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

from bench import check, counts_gcn_deep, reference_gcn_deep, trace
from bench.modes import train as loop
from bench.traffic import gcn
from bench.traffic import gcn_deep as traffic

FIRST_STEPS = loop.FIRST_STEPS
#: the configuration keys this mode runs; besides them a configuration's
#: file holds only documentation (``bench.modes.train.DOC_KEYS``)
RUN_KEYS = {"n_nodes", "n_edges", "n_entries", "n_features", "n_classes",
            "layers", "hidden", "norm_eps", "norm_momentum", "dropout", "lr",
            "adam_b1", "adam_b2", "adam_eps", "train_nodes", "valid_nodes",
            "test_nodes", "dtype", "matmul_precision", "graph_seed",
            "degree_alpha"}


def check_config(cfg: dict) -> None:
    """Refuse a configuration that states a setting this mode does not
    run: a key it does not know, or a type other than float32."""
    unknown = set(cfg) - RUN_KEYS - loop.DOC_KEYS
    if unknown:
        raise ValueError(f"the deep GCN train mode does not run {sorted(unknown)}")
    if cfg["dtype"] != "float32":
        raise ValueError(f"the deep GCN train mode runs float32, not {cfg['dtype']!r}")


def build_program(cfg: dict, graph: dict, inputs: dict, *, model=None) -> dict:
    """The adjacency in the program's CSR, the schedule its selector picks,
    its feed formats (built by one eager forward, which the program
    memoizes on the matrix), and the jitted training step (not yet
    compiled).  ``model`` replaces the program's ``gcn``: the tests plant
    a fault through it."""
    import jax
    import jax.numpy as jnp

    from repro.fuse import BatchNorm
    from repro.models.layers import gcn as program_gcn
    from repro.sparse import CSR, Schedule, matrix_stats
    from repro.train.optimizer import AdamW

    check_config(cfg)
    model = model or program_gcn
    adj = CSR(indptr=jnp.asarray(graph["indptr"]),
              indices=jnp.asarray(graph["indices"]),
              vals=jnp.asarray(graph["vals"]), shape=graph["shape"])
    opt = AdamW(lr=cfg["lr"], b1=cfg["adam_b1"], b2=cfg["adam_b2"],
                eps=cfg["adam_eps"], weight_decay=0.0, clip_norm=None)
    norm = BatchNorm(eps=cfg["norm_eps"], momentum=cfg["norm_momentum"])
    t0 = time.perf_counter()
    sched = Schedule.auto(matrix_stats(adj), cfg["hidden"])
    jax.block_until_ready(model(adj, inputs["x"], inputs["params"], norm=norm,
                                stats=inputs["stats"], schedule=sched))
    format_build_s = time.perf_counter() - t0

    def loss_fn(p, stats, x, y, train, key):
        logits, stats = model(adj, x, p, norm=norm, stats=stats,
                              keeps=traffic.masks(key, cfg), schedule=sched)
        logp = jax.nn.log_softmax(logits[train], axis=-1)
        nll = -jnp.mean(jnp.take_along_axis(logp, y[train][:, None], axis=1))
        return nll, stats

    def step(p, state, x, y, train, key):
        opt_state, stats = state
        (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, stats, x, y, train, gcn.step_key(key, opt_state.step))
        p, opt_state, _ = opt.update(grads, opt_state, p)
        return p, (opt_state, stats), loss

    return {"step": jax.jit(step, donate_argnums=(0, 1)), "opt": opt,
            "adjacency": adj, "schedule": sched,
            "format_build_s": format_build_s}


def initial_state(program: dict, inputs: dict, params) -> tuple:
    """The optimizer's fresh state and the run's initial running
    statistics, as the step carries them."""
    import jax

    return (program["opt"].init(params),
            jax.tree.map(lambda a: a.copy(), inputs["stats"]))


def compile_step(program: dict, inputs: dict) -> dict:
    """Lower and compile the step for this run's shapes; records the
    compile time and the step's HLO text."""
    t0 = time.perf_counter()
    compiled = program["step"].lower(
        inputs["params"], initial_state(program, inputs, inputs["params"]),
        *loop.feed(inputs)).compile()
    return {**program, "compiled": compiled,
            "compile_s": time.perf_counter() - t0, "hlo": compiled.as_text()}


def first_steps(program: dict, inputs: dict, steps: int = FIRST_STEPS):
    """The GAT training mode's first steps (``bench.modes.train_gat``):
    losses, the first gradient as Adam holds it, and the parameters
    before each step and after the last, with the live state."""
    import jax

    from bench.reference import to_host

    step, b1 = program["compiled"], program["opt"].b1
    # the step donates its state: the run's initial parameters stay
    # whole for the reference
    params = jax.tree.map(lambda a: a.copy(), inputs["params"])
    snap = {"params0": to_host(params), "losses": []}
    snap["params"] = [snap["params0"]]
    state = initial_state(program, inputs, params)
    args = loop.feed(inputs)
    for t in range(steps):
        params, state, loss = step(params, state, *args)
        snap["losses"].append(float(jax.block_until_ready(loss)))
        snap["params"].append(to_host(params))
        if t == 0:
            snap["grad1"] = {k: v / (1.0 - b1)
                             for k, v in to_host(state[0].mu).items()}
    snap["params1"], snap["params_end"] = snap["params"][1], snap["params"][-1]
    return snap, (params, state)


def run(ctx: dict) -> dict:
    """One run of a deep GCN training cell; see ``bench/run.py`` for ``ctx``."""
    import jax

    # a program without the L-layer GCN fails here, before any set-up
    from repro.models.layers import gcn as _  # noqa: F401

    cfg, seed = ctx["config"], ctx["seed"]
    laps = {"start": ctx["t_chips"] - ctx["t_start"]}
    t = ctx["t_chips"]

    def lap(name):
        nonlocal t
        laps[name], t = time.perf_counter() - t, time.perf_counter()

    graph = gcn.config_graph(cfg)
    lap("graph")
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        inputs = traffic.make_inputs(cfg, graph, seed)
        lap("inputs")
        program = build_program(cfg, graph, inputs, model=ctx.get("model"))
        lap("formats")
        program = compile_step(program, inputs)
        lap("compile")
    snap, live = first_steps(program, inputs)
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
                times, span_s, last = loop.window(
                    program, live, inputs, min(ctx["seconds"], loop.TRACE_SECONDS))
            jax.profiler.stop_trace()
            trace_red = trace.reduce_dir(logdir)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        breakdown = trace.breakdown(trace_red)
    else:
        times, span_s, last = loop.window(program, live, inputs, ctx["seconds"])
    gc.unfreeze()

    final_loss = float(last[2])
    peak = (ctx["device"].memory_stats() or {}).get("peak_bytes_in_use")
    del live, last, program["compiled"]

    ref = reference_gcn_deep.first_steps(cfg, graph, inputs, steps=FIRST_STEPS,
                                         follow=snap)
    ok, shown = check.verdict(check.numbers(snap, ref), ctx["limits"])
    attempted = len(times)
    failed = 0 if np.isfinite(final_loss) else attempted
    launches = trace.pallas_launches(program["hlo"])
    record = {
        "steps": attempted, "config": cfg, "peak": ctx["peak"],
        "hlo": program["hlo"], "compile_s": program["compile_s"],
        "format_build_s": program["format_build_s"], "trace": trace_red,
        "model_flops": counts_gcn_deep.train_flops(cfg),
        "adjacency": program["adjacency"], "schedule": program["schedule"],
    }
    end_to_end = {"step_ms": span_s / attempted * 1e3,
                  "step_p95_ms": loop.p95(times) * 1e3, "setup_s": setup_s}
    return {"correct": bool(ok and failed == 0), "attempted": attempted,
            "failed": failed, "end_to_end": end_to_end, "record": record,
            "memory_peak_bytes": peak, "breakdown": breakdown, "checks": shown,
            "notes": {"schedule": str(program["schedule"]),
                      "losses": snap["losses"],
                      "reference_losses": ref["losses"], "setup_laps_s": laps,
                      "slow_steps": loop.slow_steps(times),
                      "launches": [lc["name"] for lc in launches]}}
