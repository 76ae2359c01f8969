"""Full-batch GAT training, closed loop: one caller steps back to back.

Set-up builds the compiled training step and drives it from the seed
through its first steps; the measured window goes on stepping the same
object.  Each step is the program's ``gat_two_layer`` (each layer's
projection and score terms in XLA, its attention through the fused
forward kernel and, under the custom VJP, the fused backward kernel)
inside ``jax.value_and_grad`` of the masked cross-entropy with L2, then
the program's ``AdamW`` update, in one jitted call that ends in
``block_until_ready``.  The dropout masks of the features and of the
coefficients are drawn in the step from the run's key and the step
count (``bench.traffic.gat.masks``).  The losses, the first gradient and
the parameters after the first step and after the first steps are
compared with the float32 reference (``bench.reference_gat``) once the
window has closed; the reference takes each of its steps from the
program's parameters before that step.  The loop itself is the GCN
training mode's (``bench.modes.train``).
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

from bench import check, counts_gat, reference_gat, trace
from bench.modes import train as loop
from bench.traffic import gat as traffic
from bench.traffic import gcn

FIRST_STEPS = loop.FIRST_STEPS
#: the configuration keys this mode runs; besides them a configuration's
#: file holds only documentation (``bench.modes.train.DOC_KEYS``)
RUN_KEYS = {"n_nodes", "n_edges", "n_entries", "n_features", "n_classes",
            "heads", "hidden", "out_heads", "slope", "input_dropout",
            "coef_dropout", "weight_decay", "lr", "adam_b1", "adam_b2",
            "adam_eps", "train_per_class", "dtype", "matmul_precision",
            "graph_seed", "degree_alpha"}


def check_config(cfg: dict) -> None:
    """Refuse a configuration that states a setting this mode does not
    run: a key it does not know, or a type other than float32."""
    unknown = set(cfg) - RUN_KEYS - loop.DOC_KEYS
    if unknown:
        raise ValueError(f"the GAT train mode does not run {sorted(unknown)}")
    if cfg["dtype"] != "float32":
        raise ValueError(f"the GAT train mode runs float32, not {cfg['dtype']!r}")


def build_program(cfg: dict, graph: dict, *, gat=None) -> dict:
    """The pattern, and the jitted training step (not yet compiled).
    ``gat`` replaces the program's ``gat_two_layer``: the tests plant a
    fault through it."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import gat_two_layer
    from repro.train.optimizer import AdamW

    check_config(cfg)
    gat = gat or gat_two_layer
    rows, cols = jnp.asarray(graph["rows"]), jnp.asarray(graph["indices"])
    pattern = (rows, cols, cfg["n_nodes"])
    nnz = int(rows.shape[0])
    opt = AdamW(lr=cfg["lr"], b1=cfg["adam_b1"], b2=cfg["adam_b2"],
                eps=cfg["adam_eps"], weight_decay=0.0, clip_norm=None)
    wd = cfg["weight_decay"]

    def loss_fn(p, x, y, train, key):
        logits = gat(pattern, x, p, slope=cfg["slope"],
                     keeps=traffic.masks(key, cfg, nnz))
        logp = jax.nn.log_softmax(logits[train], axis=-1)
        nll = -jnp.mean(jnp.take_along_axis(logp, y[train][:, None], axis=1))
        return nll + 0.5 * wd * sum(jnp.sum(p[k] ** 2) for k in traffic.WEIGHTS)

    def step(p, state, x, y, train, key):
        loss, grads = jax.value_and_grad(loss_fn)(
            p, x, y, train, gcn.step_key(key, state.step))
        p, state, _ = opt.update(grads, state, p)
        return p, state, loss

    # the pattern is all the program builds before tracing: no formats
    return {"step": jax.jit(step, donate_argnums=(0, 1)), "opt": opt,
            "schedule": "default", "format_build_s": 0.0}


def first_steps(program: dict, inputs: dict, steps: int = FIRST_STEPS):
    """The GCN training mode's first steps (``bench.modes.train``), with
    the parameters before each step and after the last under ``params``,
    for the reference to follow."""
    import jax

    from bench.reference import to_host

    step, b1 = program["compiled"], program["opt"].b1
    # the step donates its state: the run's initial parameters stay
    # whole for the reference
    params = jax.tree.map(lambda a: a.copy(), inputs["params"])
    snap = {"params0": to_host(params), "losses": []}
    snap["params"] = [snap["params0"]]
    state = program["opt"].init(params)
    args = loop.feed(inputs)
    for t in range(steps):
        params, state, loss = step(params, state, *args)
        snap["losses"].append(float(jax.block_until_ready(loss)))
        snap["params"].append(to_host(params))
        if t == 0:
            snap["grad1"] = {k: v / (1.0 - b1)
                             for k, v in to_host(state.mu).items()}
    snap["params1"], snap["params_end"] = snap["params"][1], snap["params"][-1]
    return snap, (params, state)


def run(ctx: dict) -> dict:
    """One run of a GAT training cell; see ``bench/run.py`` for ``ctx``."""
    import jax

    cfg, seed = ctx["config"], ctx["seed"]
    laps = {"start": ctx["t_chips"] - ctx["t_start"]}
    t = ctx["t_chips"]

    def lap(name):
        nonlocal t
        laps[name], t = time.perf_counter() - t, time.perf_counter()

    graph = gcn.config_graph(cfg)
    lap("graph")
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        program = build_program(cfg, graph, gat=ctx.get("gat"))
        inputs = traffic.make_inputs(cfg, graph, seed)
        lap("inputs")
        program = loop.compile_step(program, inputs)
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

    ref = reference_gat.first_steps(cfg, graph, inputs, steps=FIRST_STEPS,
                                    follow=snap)
    ok, shown = check.verdict(check.numbers(snap, ref), ctx["limits"])
    attempted = len(times)
    failed = 0 if np.isfinite(final_loss) else attempted
    record = {
        "steps": attempted, "config": cfg, "peak": ctx["peak"],
        "hlo": program["hlo"], "compile_s": program["compile_s"],
        "format_build_s": program["format_build_s"], "trace": trace_red,
        "model_flops": counts_gat.gat_train_flops(cfg),
    }
    end_to_end = {"step_ms": span_s / attempted * 1e3,
                  "step_p95_ms": loop.p95(times) * 1e3, "setup_s": setup_s}
    return {"correct": bool(ok and failed == 0), "attempted": attempted,
            "failed": failed, "end_to_end": end_to_end, "record": record,
            "memory_peak_bytes": peak, "breakdown": breakdown, "checks": shown,
            "notes": {"schedule": program["schedule"], "losses": snap["losses"],
                      "reference_losses": ref["losses"], "setup_laps_s": laps,
                      "slow_steps": loop.slow_steps(times),
                      "attention_launches": [
                          lc["kernel"] for lc in counts_gat.attn_launches(program["hlo"])]}}
