import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402  (the XLA_FLAGS line above must precede ANY jax import)
"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production mesh and record memory / cost / collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import functools
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import (ARCHS, SHAPES, cell_is_runnable, decode_specs,
                       get_config, train_batch_specs)
from ..distributed.sharding import (batch_shardings, cache_shardings,
                                    data_axes, param_shardings, replicated)
from ..models import get_model
from ..models.moe import ShardingCtx
from ..roofline.analysis import (analyze, combine_costs, count_active_params,
                                 count_params, extract_costs)
from ..train.optimizer import AdamW, cosine_schedule
from ..train.train_step import TrainState, init_state, make_train_step
from .mesh import make_production_mesh

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def zero1_shardings(mesh, params_shape, pshard):
    """ZeRO-1: shard optimizer moments over the data axes on the first
    still-unsharded, divisible dim of each param."""
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]

    def rule(leaf, psh):
        spec = list(psh.spec) + [None] * (len(leaf.shape) - len(psh.spec))
        used = set()
        for cur in spec:
            for a in (cur if isinstance(cur, tuple) else (cur,)):
                if a is not None:
                    used.add(a)
        if used & set(dp):  # already data-sharded (e.g. FSDP attention)
            return psh
        for dim, cur in enumerate(spec):
            if cur is None and leaf.shape[dim] % dp_size == 0:
                spec[dim] = dp
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(rule, params_shape, pshard)


def make_ctx(cfg, mesh):
    if cfg.family == "moe":
        return ShardingCtx(mesh=mesh, data_axes=data_axes(mesh),
                           model_axis="model")
    return None


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               zero1: bool = True, overrides: dict | None = None,
               microbatches: int = 8, grad_compression: str | None = None):
    """Build and lower one (arch × shape × mesh) cell. Returns
    (lowered, meta) without compiling."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.scaled(**overrides)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return None, {"skipped": why, "arch": arch, "shape": shape_name}

    api = get_model(cfg)
    ctx = make_ctx(cfg, mesh)
    params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    pshard = param_shardings(mesh, params_shape)
    n_chips = mesh.devices.size

    meta = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "n_chips": n_chips,
        "tokens": (shape.global_batch if shape.kind == "decode"
                   else shape.tokens),
        "n_params": count_params(params_shape),
        "n_active_params": count_active_params(params_shape, cfg),
    }

    if shape.kind == "train":
        opt = AdamW(lr=cosine_schedule(3e-4, 2000, 100_000))
        state_shape = jax.eval_shape(
            functools.partial(init_state, api, opt), jax.random.PRNGKey(0))
        mom_shard = (zero1_shardings(mesh, params_shape, pshard)
                     if zero1 else pshard)
        state_sh = TrainState(
            params=pshard,
            opt=type(state_shape.opt)(step=replicated(mesh), mu=mom_shard,
                                      nu=mom_shard))
        specs = train_batch_specs(cfg, shape)
        bsh = batch_shardings(mesh, specs)
        # microbatched grad accumulation: the production knob that bounds
        # per-layer activation residuals (B_loc/µB per microbatch).
        step_fn = make_train_step(api, opt, ctx, microbatches=microbatches,
                                  grad_compression=grad_compression)
        metrics_sh = {k: replicated(mesh)
                      for k in ("loss", "grad_norm", "step")}
        with mesh:
            lowered = jax.jit(
                step_fn,
                in_shardings=(state_sh, bsh),
                out_shardings=(state_sh, metrics_sh),
                donate_argnums=(0,),
            ).lower(state_shape, specs)
        return lowered, meta

    if shape.kind == "prefill":
        specs = train_batch_specs(cfg, shape)
        bsh = batch_shardings(mesh, specs)
        max_len = shape.seq_len

        def prefill_fn(params, batch):
            return api.prefill(params, batch, max_len)

        cache_shape = jax.eval_shape(
            lambda: api.init_cache(shape.global_batch, max_len))
        csh = cache_shardings(mesh, cfg, cache_shape)
        logits_sh = NamedSharding(
            mesh, P(data_axes(mesh),
                    "model" if cfg.vocab_size % mesh.shape["model"] == 0
                    else None))
        with mesh:
            lowered = jax.jit(
                prefill_fn,
                in_shardings=(pshard, bsh),
                out_shardings=(logits_sh, csh),
            ).lower(params_shape, specs)
        return lowered, meta

    # decode
    specs = decode_specs(cfg, shape, api.init_cache)
    cache_shape = specs["cache"]
    csh = cache_shardings(mesh, cfg, cache_shape)
    dp = data_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tok_sh = NamedSharding(
        mesh, P(dp if shape.global_batch % dp_size == 0 else None))
    logits_sh = NamedSharding(
        mesh, P(dp if shape.global_batch % dp_size == 0 else None,
                "model" if cfg.vocab_size % mesh.shape["model"] == 0
                else None))

    def serve_step(params, cache, tokens):
        return api.decode_step(params, cache, tokens)

    with mesh:
        lowered = jax.jit(
            serve_step,
            in_shardings=(pshard, csh, tok_sh),
            out_shardings=(logits_sh, csh),
            donate_argnums=(1,),
        ).lower(params_shape, cache_shape, specs["tokens"])
    return lowered, meta


def _ladder(arch, shape_name, *, multi_pod, zero1, n_layers, family,
            extra_overrides, overrides=None, microbatches=8,
            grad_compression=None):
    """XLA cost analysis counts scan bodies once; compile L=1 and L=2
    variants (with unrolled layer scans) and extrapolate:
    total = cost(1) + (L-1)·(cost(2)-cost(1)). Exact for
    scan-homogeneous layer stacks (all of ours)."""
    per_l = {}
    for l_val in (1, 2):
        ov = dict(overrides or {})
        ov.update(n_layers=l_val, scan_unroll=True, ssd_unroll=True)
        ov.update(extra_overrides)
        if family == "encdec":
            ov["n_encoder_layers"] = l_val
        # microbatches=1 for measurement: the grad-accum scan is a while
        # loop whose body XLA cost analysis counts once; the single-batch
        # step has identical total flops/collective bytes.
        lowered, _ = lower_cell(arch, shape_name, multi_pod=multi_pod,
                                zero1=zero1, overrides=ov, microbatches=1,
                                grad_compression=grad_compression)
        per_l[l_val] = extract_costs(lowered.compile())
    body = {
        "flops": per_l[2]["flops"] - per_l[1]["flops"],
        "bytes": per_l[2]["bytes"] - per_l[1]["bytes"],
        "coll_bytes": per_l[2]["coll_bytes"] - per_l[1]["coll_bytes"],
        "collectives": {
            op: {"count": per_l[2]["collectives"].get(op, {"count": 0})["count"]
                 - per_l[1]["collectives"].get(op, {"count": 0})["count"],
                 "bytes": per_l[2]["collectives"].get(op, {"bytes": 0})["bytes"]
                 - per_l[1]["collectives"].get(op, {"bytes": 0})["bytes"]}
            for op in set(per_l[2]["collectives"]) | set(per_l[1]["collectives"])
        },
    }
    return combine_costs(per_l[1], body, n_layers - 1)


def _extrapolated_costs(arch, shape_name, *, multi_pod, zero1, n_layers,
                        family, overrides=None, microbatches=8,
                        grad_compression=None):
    """Two measurement ladders (DESIGN.md §9):

    flops  — single-trip attention chunks (q/kv_chunk = big): identical
             math, every matmul visible to cost analysis. Exact.
    bytes/ — default chunked attention: the single-chunk module would
    colls    "write" the S² score matrix to HBM, wildly inflating the
             memory term vs the flash structure where score blocks stay
             in VMEM. Chunk-loop bodies are counted once, matching one
             streaming pass over q/k/v — the flash HBM traffic model.
    """
    common = dict(arch=arch, shape_name=shape_name, multi_pod=multi_pod,
                  zero1=zero1, n_layers=n_layers, family=family,
                  overrides=overrides, microbatches=microbatches,
                  grad_compression=grad_compression)
    flop_costs = _ladder(extra_overrides={"q_chunk": 1 << 22,
                                          "kv_chunk": 1 << 22}, **common)
    byte_costs = _ladder(extra_overrides={}, **common)
    return {
        "flops": flop_costs["flops"],
        "bytes": byte_costs["bytes"],
        "coll_bytes": byte_costs["coll_bytes"],
        "collectives": byte_costs["collectives"],
    }


def run_cell(arch, shape_name, *, multi_pod, zero1=True, save=True,
             overrides=None, tag=None, microbatches=8,
             grad_compression=None):
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, multi_pod=multi_pod,
                               zero1=zero1, overrides=overrides,
                               microbatches=microbatches,
                               grad_compression=grad_compression)
    if tag:
        meta["tag"] = tag
    if lowered is None:
        print(f"SKIP {arch} × {shape_name}: {meta['skipped']}")
        if save:
            _save(meta)
        return meta
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    cfg = get_config(arch)
    costs = _extrapolated_costs(
        arch, shape_name, multi_pod=multi_pod, zero1=zero1,
        n_layers=(cfg.n_layers if not overrides
                  else overrides.get("n_layers", cfg.n_layers)),
        family=cfg.family, overrides=overrides, microbatches=microbatches,
        grad_compression=grad_compression)
    res = analyze(costs, compiled.memory_analysis(),
                  n_chips=meta["n_chips"], kind=meta["kind"],
                  tokens=meta["tokens"], n_params=meta["n_params"],
                  n_active_params=meta["n_active_params"])
    res["uncorrected_scan_once"] = extract_costs(compiled)
    res.update(meta)
    res["t_lower_s"] = round(t_lower, 2)
    res["t_compile_s"] = round(t_compile, 2)
    print(f"OK {arch} × {shape_name} × {res['mesh']}: "
          f"flops/chip={res['per_chip']['hlo_flops']:.3e} "
          f"coll={res['per_chip']['collective_bytes']:.3e}B "
          f"dom={res['dominant']} frac={res['roofline_fraction']:.3f} "
          f"(lower {t_lower:.1f}s compile {t_compile:.1f}s)")
    if save:
        _save(res)
    return res


def _save(res):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    tag = f"__{res['tag']}" if res.get("tag") else ""
    name = f"{res['arch']}__{res['shape']}__{res.get('mesh', 'skip')}{tag}.json"
    (OUT_DIR / name).write_text(json.dumps(res, indent=2, default=float))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-zero1", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    failures = []
    for a, s in cells:
        try:
            run_cell(a, s, multi_pod=args.multi_pod,
                     zero1=not args.no_zero1)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            failures.append((a, s, repr(e)))
    if failures:
        print("FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
