"""§Perf hillclimb driver: re-run selected cells with optimization
variants and print before/after roofline terms.

    PYTHONPATH=src python -m repro.launch.hillclimb [--cell arch:shape:tag]
    PYTHONPATH=src python -m repro.launch.hillclimb --spmm [--n-dense 4]
    PYTHONPATH=src python -m repro.launch.hillclimb --moe
    PYTHONPATH=src python -m repro.launch.hillclimb --attention
    PYTHONPATH=src python -m repro.launch.hillclimb --dist

``--moe`` does the same for the MoE grouped-matmul dispatch space
(token_tile × capacity × f_tile × d_tile, keyed by the expert-segment
histogram) — populating the per-backend cache ahead of serving.
``--attention`` covers the fused-attention tuner (fwd and bwd records),
and ``--dist`` the joint collective × tiling × value-dtype distributed
SpMM search — together the four flags pre-warm every tuner surface the
serving resolvers replay.

``--spmm`` hillclimbs *schedules* instead of cfg knobs: it runs the
empirical autotuner (``repro.tune``) over the synthetic matrix suite,
consulting and populating the persistent fingerprint cache
(``REPRO_TUNE_CACHE``) — a second run replays every cell for free, and
serving (``ServeEngine.spmm``) picks the tuned schedules up from the
same cache.  Prints auto (static selector) vs tuned wall clock per cell.

Variants for the roofline mode are cfg-level knobs (tags):
    sp        seq_parallel_attn=True (Megatron-SP attention)
    inplace   decode_inplace_cache=True (fori_loop cache, no double buffer)
    mb16      microbatches=16
    nochunkkv kv_chunk=2048 (bigger flash kv tiles)

The roofline mode imports ``.dryrun``, which forces a 512-device host
platform *at import* — that is why it is imported lazily per mode:
``--spmm`` must measure under the same single-device XLA environment the
serving process that replays the cache will run under.
"""
import argparse
import json

VARIANTS = {
    "sp": {"overrides": {"seq_parallel_attn": True}},
    "gc_bf16": {"grad_compression": "bf16"},
    "sp_gc": {"overrides": {"seq_parallel_attn": True},
              "grad_compression": "bf16"},
    "sp_mb4": {"overrides": {"seq_parallel_attn": True}, "microbatches": 4},
    "inplace": {"overrides": {"decode_inplace_cache": True}},
    "sp_inplace": {"overrides": {"seq_parallel_attn": True,
                                 "decode_inplace_cache": True}},
    "mb16": {"microbatches": 16},
    "kv2048": {"overrides": {"kv_chunk": 2048}},
}

# The three hillclimbed cells (chosen per assignment criteria from the
# baseline grid):
#   qwen3-moe train_4k      — most representative of the paper's technique
#                             (segment-group MoE dispatch) + memory-dom
#                             with useful=0.07 (attention replication);
#   deepseek prefill_32k    — most collective-bound (coll/mem = 2.8);
#   deepseek decode_32k     — decode memory floor (cache double-buffer).
DEFAULT_PLAN = [
    ("qwen3-moe-235b-a22b", "train_4k", ["sp"]),
    ("deepseek-coder-33b", "prefill_32k", ["sp", "kv2048"]),
    ("deepseek-coder-33b", "decode_32k", ["inplace"]),
    ("qwen2-7b", "train_4k", ["sp", "gc_bf16", "sp_gc"]),
]


def compare(arch, shape, tag):
    from .dryrun import OUT_DIR

    base = json.loads(
        (OUT_DIR / f"{arch}__{shape}__16x16.json").read_text())
    opt = json.loads(
        (OUT_DIR / f"{arch}__{shape}__16x16__{tag}.json").read_text())
    print(f"--- {arch} × {shape} [{tag}] ---")
    for key in ("compute", "memory", "collective"):
        b, o = base["terms_s"][key], opt["terms_s"][key]
        print(f"  {key:10s} {b * 1e3:9.1f} ms -> {o * 1e3:9.1f} ms "
              f"({b / max(o, 1e-12):.2f}x)")
    tb = base["per_chip"]["temp_bytes"] / 1e9
    to = opt["per_chip"]["temp_bytes"] / 1e9
    print(f"  temp       {tb:9.2f} GB -> {to:9.2f} GB")
    print(f"  frac       {base['roofline_fraction']:.4f} -> "
          f"{opt['roofline_fraction']:.4f}")


def spmm_hillclimb(n_dense: int = 4, quick: bool = True):
    """Tune schedules for the synthetic suite through the persistent
    cache; print auto-vs-tuned per cell and the geomean win."""
    import numpy as np

    from repro.core import Schedule
    from repro.sparse import matrix_stats, random_csr
    from repro.tune import default_cache, measure_schedule, tune_schedule

    cache = default_cache()
    cells = [(1024 if quick else 4096, d, s)
             for d in (0.002, 0.01) for s in (0.0, 1.5)]
    wins = []
    for m, d, s in cells:
        csr = random_csr(m, m, density=d, skew=s, seed=int(s * 10))
        res = tune_schedule(csr, n_dense, cache=cache)
        auto = Schedule.auto(matrix_stats(csr), n_dense)
        t_auto = measure_schedule(csr, n_dense, auto) * 1e6
        wins.append(t_auto / max(res.us_per_call, 1e-9))
        src = "cache" if res.from_cache else f"{res.n_measurements} meas"
        print(f"--- spmm {m}x{m} d={d} skew={s} N={n_dense} [{src}] ---")
        print(f"  auto  {auto}: {t_auto:9.1f} us")
        print(f"  tuned {res.schedule}: {res.us_per_call:9.1f} us "
              f"({wins[-1]:.2f}x)")
    print(f"geomean tuned-vs-auto: "
          f"{float(np.exp(np.mean(np.log(np.maximum(wins, 1e-9))))):.3f}x "
          f"({len(cache)} records in {cache.path})")


def moe_hillclimb(quick: bool = True):
    """Tune MoE dispatch schedules for representative expert histograms
    (balanced and skewed routing) through the persistent per-backend
    cache; print default-vs-tuned per cell and the geomean win.  Serving
    (``ServeEngine.moe_dispatch_schedule``) picks the results up from
    the same cache with zero measurements."""
    import numpy as np

    from repro.configs import ARCHS, smoke_config
    from repro.models.moe import (balanced_expert_lengths, default_dispatch,
                                  moe_tune_dispatch, skewed_expert_lengths)
    from repro.tune import default_cache
    from repro.tune.moe import measure_moe_dispatch, moe_schedule_key

    cfg = smoke_config(ARCHS["qwen3-moe-235b-a22b"]).scaled(
        d_model=128 if quick else 256, moe_d_ff=128 if quick else 512,
        n_experts=8, experts_per_token=2)
    cache = default_cache()
    cells = []
    for t in ((512,) if quick else (512, 2048)):
        cells.append((f"balanced_t{t}", t, balanced_expert_lengths(cfg, t)))
        cells.append((f"skewed_t{t}", t, skewed_expert_lengths(cfg, t)))

    wins = []
    for name, t, lengths in cells:
        res = moe_tune_dispatch(cfg, t, expert_lengths=lengths, cache=cache)
        base = default_dispatch(cfg)
        # the default is always in the tuner's measured pool; only a
        # cache-hit replay (which measured nothing) times it afresh
        t_base = res.measured.get(moe_schedule_key(base))
        if t_base is None:
            t_base = measure_moe_dispatch(
                lengths, cfg.d_model, cfg.moe_d_ff, base,
                dtype=str(cfg.param_dtype), max_tokens=t) * 1e6
        wins.append(t_base / max(res.us_per_call, 1e-9))
        src = "cache" if res.from_cache else f"{res.n_measurements} meas"
        print(f"--- moe {name} E={cfg.n_experts} D={cfg.d_model} "
              f"F={cfg.moe_d_ff} [{src}] ---")
        print(f"  default {base}: {t_base:9.1f} us")
        print(f"  tuned   {res.schedule}: {res.us_per_call:9.1f} us "
              f"({wins[-1]:.2f}x)")
    print(f"geomean tuned-vs-default: "
          f"{float(np.exp(np.mean(np.log(np.maximum(wins, 1e-9))))):.3f}x "
          f"({len(cache)} records in {cache.path})")


def attention_hillclimb(quick: bool = True):
    """Tune the fused-attention kernels (fwd and bwd) for representative
    sparsity patterns through the persistent per-backend cache, so
    training/serving loops replay them measurement-free."""
    import jax
    import numpy as np

    from repro.sparse import random_csr
    from repro.tune import default_cache, tune_sparse_attention

    cache = default_cache()
    n = 256 if quick else 1024
    d = dv = 16 if quick else 64
    cells = [("uniform", 0.0), ("skewed", 1.5)]
    for name, skew in cells:
        coo = random_csr(n, n, density=0.05, skew=skew,
                         seed=int(skew * 10)).tocoo()
        kq = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq[0], (n, d))
        k = jax.random.normal(kq[1], (n, d))
        v = jax.random.normal(kq[2], (n, dv))
        for direction in ("fwd", "bwd"):
            res = tune_sparse_attention(
                np.asarray(coo.rows), np.asarray(coo.cols), q, k, v,
                n_rows=n, direction=direction, cache=cache)
            src = ("cache" if res.from_cache
                   else f"{res.n_measurements} meas")
            print(f"--- attn {name} {n}x{n} d={d} {direction} [{src}] ---")
            print(f"  tuned {res.schedule}: {res.us_per_call:9.1f} us")
    print(f"({len(cache)} records in {cache.path})")


def dist_hillclimb(n_dense: int = 4, quick: bool = True):
    """Joint collective × tiling × value-dtype tuning for sharded SpMM
    on the local mesh (§14's joint axis search), populating the same
    per-backend cache ``dist_spmm(..., schedule='tune')`` and
    ``ServeEngine.prepare_dist`` replay from."""
    from repro.launch.mesh import make_reduction_mesh
    from repro.sparse import random_csr
    from repro.tune import default_cache, tune_dist_spmm

    cache = default_cache()
    mesh = make_reduction_mesh()
    axis_size = int(mesh.shape["shards"])
    n = 512 if quick else 2048
    for d in (0.002, 0.01):
        csr = random_csr(n, n, density=d, seed=7)
        res = tune_dist_spmm(csr, n_dense, mesh=mesh, axis="shards",
                             cache=cache)
        src = "cache" if res.from_cache else f"{res.n_measurements} meas"
        print(f"--- dist {n}x{n} d={d} mesh={axis_size} [{src}] ---")
        print(f"  tuned {res.schedule}: {res.us_per_call:9.1f} us "
              f"(collective={res.schedule.collective}, "
              f"value_dtype={res.schedule.value_dtype})")
    print(f"({len(cache)} records in {cache.path})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", action="append", default=None,
                    help="arch:shape:tag (repeatable)")
    ap.add_argument("--spmm", action="store_true",
                    help="hillclimb sparse schedules via the autotuner "
                         "(populates the persistent tuner cache)")
    ap.add_argument("--moe", action="store_true",
                    help="tune MoE grouped-matmul dispatch schedules "
                         "(populates the same per-backend tuner cache)")
    ap.add_argument("--attention", action="store_true",
                    help="tune the fused attention kernels (fwd+bwd) so "
                         "training/serving replay measurement-free")
    ap.add_argument("--dist", action="store_true",
                    help="joint collective × dtype tuning for sharded "
                         "SpMM on the local mesh")
    ap.add_argument("--n-dense", type=int, default=4)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()

    if args.spmm:
        spmm_hillclimb(args.n_dense, quick=not args.full)
        return
    if args.moe:
        moe_hillclimb(quick=not args.full)
        return
    if args.attention:
        attention_hillclimb(quick=not args.full)
        return
    if args.dist:
        dist_hillclimb(args.n_dense, quick=not args.full)
        return

    # roofline mode: importing .dryrun forces the 512-device host platform
    from .dryrun import run_cell

    plan = []
    if args.cell:
        for c in args.cell:
            arch, shape, tag = c.split(":")
            plan.append((arch, shape, [tag]))
    else:
        plan = DEFAULT_PLAN

    for arch, shape, tags in plan:
        for tag in tags:
            v = VARIANTS[tag]
            run_cell(arch, shape, multi_pod=False,
                     overrides=v.get("overrides"),
                     microbatches=v.get("microbatches", 8),
                     grad_compression=v.get("grad_compression"), tag=tag)
            try:
                compare(arch, shape, tag)
            except FileNotFoundError:
                print(f"(no baseline for {arch} × {shape} yet)")


if __name__ == "__main__":
    main()
