"""Beyond-paper benchmarks: MoE segment-group dispatch and the data-aware
selector's prediction quality.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, smoke_config
from repro.core import select_schedule
from repro.models.moe import apply_moe, init_moe
from repro.sparse.random import matrix_stats

from ._util import geomean, make_runner, suite, time_fn


def moe_dispatch(quick=True):
    """Capacity/segment dispatch (grouped GEMM over per-expert segments)
    vs the naive per-token weight-gather formulation."""
    cfg = smoke_config(ARCHS["qwen3-moe-235b-a22b"]).scaled(
        d_model=256, moe_d_ff=256, n_experts=8, experts_per_token=2)
    p = init_moe(cfg, jax.random.PRNGKey(0))
    t_tokens = 1024 if quick else 8192
    x = jax.random.normal(jax.random.PRNGKey(1), (t_tokens, cfg.d_model))

    seg = jax.jit(lambda p, x: apply_moe(cfg, p, x, None)[0])

    def naive(p, x):
        logits = x @ p["router"]
        probs = jax.nn.softmax(logits, -1)
        topv, topi = jax.lax.top_k(probs, cfg.experts_per_token)
        topv = topv / topv.sum(-1, keepdims=True)
        wg = p["wg"][topi]  # (T, k, D, F) weight gather — the naive path
        wi = p["wi"][topi]
        wo = p["wo"][topi]
        h = jax.nn.silu(jnp.einsum("td,tkdf->tkf", x, wg)) * jnp.einsum(
            "td,tkdf->tkf", x, wi)
        y = jnp.einsum("tkf,tkfd->tkd", h, wo)
        return jnp.einsum("tkd,tk->td", y, topv)

    naive_j = jax.jit(naive)
    t_seg = time_fn(seg, p, x)
    t_naive = time_fn(naive_j, p, x)
    return [("beyond/moe_dispatch", t_seg * 1e6,
             f"speedup_vs_weight_gather={t_naive / t_seg:.3f}")]


def moe_tuner_gap(quick=True):
    """Tuned-vs-default MoE dispatch (ISSUE 3): tune the token-tile ×
    capacity × (f_tile, d_tile) space per expert histogram (memory-only
    cache) and report the measured win over the static default point."""
    from repro.models.moe import (balanced_expert_lengths, default_dispatch,
                                  moe_tune_dispatch, skewed_expert_lengths)
    from repro.tune import ScheduleCache
    from repro.tune.moe import moe_schedule_key

    cfg = smoke_config(ARCHS["qwen3-moe-235b-a22b"]).scaled(
        d_model=128, moe_d_ff=128 if quick else 256, n_experts=8,
        experts_per_token=2)
    t_tokens = 512 if quick else 2048
    balanced = balanced_expert_lengths(cfg, t_tokens)
    skewed = skewed_expert_lengths(cfg, t_tokens)

    cache = ScheduleCache(path=None)  # never touch the user's cache
    base = default_dispatch(cfg)
    rows, wins = [], []
    for name, lengths in (("balanced", balanced), ("skewed", skewed)):
        res = moe_tune_dispatch(cfg, t_tokens, expert_lengths=lengths,
                                cache=cache, warmup=1, iters=3)
        # memory-only cache -> never a replay: the default's timing is
        # already in the tuner's own measured pool
        t_base = res.measured[moe_schedule_key(base)]
        wins.append(t_base / max(res.us_per_call, 1e-9))
        s = res.schedule
        rows.append((f"beyond/moe_tuner/{name}", res.us_per_call,
                     f"tuned=tt{s.token_tile}/cf{s.capacity_factor:g}"
                     f"/f{s.f_tile}/d{s.d_tile},default_us={t_base:.1f},"
                     f"tuned_vs_default={wins[-1]:.3f}"))
    rows.append(("beyond/moe_tuner_gap", 0.0,
                 f"tuned_vs_default_geomean={geomean(wins):.3f}"))
    return rows


def fused_attention(quick=True):
    """Fused one-pass SDDMM→segment-softmax→SpMM *kernel* vs the unfused
    3-pass kernel composition (ISSUE 4).

    Unlike the schedule benchmarks (which time jitted analogues — the
    kernel-*shape* question), fusion is a question about kernel *passes*,
    so this times the actual Pallas programs, the same way
    ``tune_segment_reduce`` times its real kernel: fused = the single
    ``kernels.fused_attention`` launch (a row-max pass, then a weighted sum);
    unfused = SDDMM kernel → segment-max kernel → exp/normalize →
    segment-sum kernel → SpMM kernel over the same pattern, with the
    (nnz,)-sized score/weight intermediates materialized between passes.
    The win grows with nnz (more per-pass traffic deleted)."""
    from repro.kernels import ops as kops
    from repro.sparse import Schedule, sparse_attention
    from repro.sparse import segment_reduce as seg_reduce
    from repro.sparse.formats import GroupedCOO, round_up

    d, dv = (32, 32) if quick else (64, 64)
    # quick mode sticks to the sizes whose win is robust to a loaded
    # machine (the CI gate consumes the geomean; larger graphs win more
    # on an idle box but flap under runner contention)
    sizes = ((256, 256), (512, 512)) if quick else \
        ((1024, 1024), (2048, 2048))
    mats = suite(sizes=sizes, densities=(0.01,), skews=(0.0, 1.5))
    sched = Schedule("eb", nnz_tile=256, group_size=32)
    rows_out, wins = [], []
    for (m, n, dens, s), csr in mats:
        coo = csr.tocoo()
        rows, cols = coo.rows, coo.cols
        nnz = csr.nnz
        q = jax.random.normal(jax.random.PRNGKey(0), (m, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (n, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (n, dv))
        scale = d ** -0.5
        nnz_pad = max(round_up(max(nnz, 1), 256), 256)

        def fused(q, k, v):
            return sparse_attention((rows, cols, m), q, k, v,
                                    schedule=sched, scale=scale)

        def unfused(q, k, v):
            from repro.sparse import sddmm as sddmm_op

            sc = sddmm_op(rows, cols, q, k) * scale          # pass 1
            mx = seg_reduce(rows, sc[:, None], m, schedule=sched,
                            op="max")[:, 0]                  # pass 2
            mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
            p = jnp.exp(sc - mx[rows])
            tot = seg_reduce(rows, p[:, None], m,
                             schedule=sched)[:, 0]           # pass 3
            w = p / jnp.maximum(tot[rows], 1e-30)
            g = GroupedCOO(rows=jnp.pad(rows, (0, nnz_pad - nnz)),
                           cols=jnp.pad(cols, (0, nnz_pad - nnz)),
                           vals=jnp.pad(w, (0, nnz_pad - nnz)),
                           shape=(m, n), nnz=nnz, nnz_tile=256)
            return kops.spmm(g, v, sched)                    # pass 4

        t_fused = time_fn(fused, q, k, v, warmup=1, iters=3)
        t_unfused = time_fn(unfused, q, k, v, warmup=1, iters=3)
        wins.append(t_unfused / max(t_fused, 1e-12))
        rows_out.append((f"beyond/fused_attention/m{m}_skew{s}",
                         t_fused * 1e6,
                         f"unfused_us={t_unfused * 1e6:.1f},"
                         f"fused_vs_unfused={wins[-1]:.3f},nnz={nnz}"))
    rows_out.append(("beyond/fused_attention_gap", 0.0,
                     f"fused_vs_unfused_geomean={geomean(wins):.3f}"))
    return rows_out


def fused_attention_bwd(quick=True):
    """Fused one-launch attention *backward* vs the spec-recompute VJP
    composed of kernel passes (ISSUE 5).

    The fused side is ``kernels.fused_attention_bwd``: one launch
    recomputing the probabilities from the forward's (m, l) residuals
    and scattering dQ by row and dK/dV by column.  The unfused side realizes the PR-4 spec-recompute VJP as the
    kernel passes training actually paid: SDDMM (score recompute) →
    segment-max → segment-sum (weights) → SDDMM (dw) → segment-sum (δ)
    → three transpose/plain SpMM passes (dV, dQ, dK) — 8 kernel
    launches with (nnz,)-sized intermediates between them.  The jitted
    pure-JAX spec VJP is reported as info alongside."""
    from repro.kernels import ops as kops
    from repro.kernels.fused_attention import (
        fused_sparse_attention,
        fused_sparse_attention_bwd,
        sparse_attention_bwd_ref,
    )
    from repro.sparse import Schedule
    from repro.sparse import sddmm as sddmm_op
    from repro.sparse import segment_reduce as seg_reduce
    from repro.sparse.formats import GroupedCOO, round_up

    d, dv = (32, 32) if quick else (64, 64)
    # same size policy as the forward bench: the CI gate consumes the
    # us geomean, so quick mode sticks to contention-robust sizes
    sizes = ((256, 256), (512, 512)) if quick else \
        ((1024, 1024), (2048, 2048))
    mats = suite(sizes=sizes, densities=(0.01,), skews=(0.0, 1.5))
    sched = Schedule("eb", nnz_tile=256, group_size=32)
    rows_out, wins = [], []
    for (m, n, dens, s), csr in mats:
        coo = csr.tocoo()
        rows, cols = coo.rows, coo.cols
        nnz = csr.nnz
        q = jax.random.normal(jax.random.PRNGKey(0), (m, d))
        k = jax.random.normal(jax.random.PRNGKey(1), (n, d))
        v = jax.random.normal(jax.random.PRNGKey(2), (n, dv))
        dout = jax.random.normal(jax.random.PRNGKey(3), (m, dv))
        scale = d ** -0.5
        nnz_pad = max(round_up(max(nnz, 1), 256), 256)
        pad = nnz_pad - nnz
        rows_p = jnp.pad(rows, (0, pad))
        cols_p = jnp.pad(cols, (0, pad))
        # the (m, l) residuals the custom VJP carries across fwd -> bwd
        ost, mst, lst = fused_sparse_attention(
            rows_p, cols_p, q[:, None], k[:, None], v[:, None], n_rows=m,
            nnz=nnz, nnz_tile=256, scale=scale,
            group_size=sched.group_size, strategy=sched.strategy)

        def fused(q, k, v, do):
            return fused_sparse_attention_bwd(
                rows_p, cols_p, q[:, None], k[:, None], v[:, None], ost,
                do[:, None], mst, lst, n_rows=m, nnz=nnz, nnz_tile=256,
                scale=scale,
                group_size=sched.group_size, strategy=sched.strategy)

        def unfused(q, k, v, do):
            sc = sddmm_op(rows, cols, q, k) * scale          # pass 1
            mx = seg_reduce(rows, sc[:, None], m, schedule=sched,
                            op="max")[:, 0]                  # pass 2
            mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
            p = jnp.exp(sc - mx[rows])
            tot = seg_reduce(rows, p[:, None], m,
                             schedule=sched)[:, 0]           # pass 3
            w = p / jnp.maximum(tot[rows], 1e-30)
            dw = sddmm_op(rows, cols, do, v)                 # pass 4
            delta = seg_reduce(rows, (w * dw)[:, None], m,
                               schedule=sched)[:, 0]         # pass 5
            ds = w * (dw - delta[rows]) * scale

            def grouped(r, c, vals, shape):
                return GroupedCOO(rows=r, cols=c,
                                  vals=jnp.pad(vals, (0, pad)),
                                  shape=shape, nnz=nnz, nnz_tile=256)

            dv_ = kops.spmm(grouped(cols_p, rows_p, w, (n, m)),
                            do, sched)                       # pass 6
            dq = kops.spmm(grouped(rows_p, cols_p, ds, (m, n)),
                           k, sched)                         # pass 7
            dk = kops.spmm(grouped(cols_p, rows_p, ds, (n, m)),
                           q, sched)                         # pass 8
            return dq, dk, dv_

        spec = jax.jit(lambda q, k, v, do: sparse_attention_bwd_ref(
            rows, cols, q, k, v, do, n_rows=m, scale=scale))
        t_fused = time_fn(fused, q, k, v, dout, warmup=1, iters=3)
        t_unfused = time_fn(unfused, q, k, v, dout, warmup=1, iters=3)
        t_spec = time_fn(spec, q, k, v, dout, warmup=1, iters=3)
        wins.append(t_unfused / max(t_fused, 1e-12))
        rows_out.append((f"beyond/fused_attention_bwd/m{m}_skew{s}",
                         t_fused * 1e6,
                         f"unfused_us={t_unfused * 1e6:.1f},"
                         f"spec_vjp_us={t_spec * 1e6:.1f},"
                         f"fused_bwd_vs_unfused={wins[-1]:.3f},nnz={nnz}"))
    rows_out.append(("beyond/fused_attention_bwd_gap", 0.0,
                     f"fused_bwd_vs_unfused_geomean={geomean(wins):.3f}"))
    return rows_out


def fusion_planner(quick=True):
    """Planner-fused vs fully-split execution of the landed chains
    (ISSUE 6): the two-layer GCN chain (spmm → ewise → spmm, 2 launches
    fused vs 2 launches + 1 XLA elementwise pass split) and the MoE
    expert-GEMM chain (grouped_matmul → ewise, 1 launch fused vs GEMM +
    XLA SiLU pass).  Each row times ``run_plan`` on the greedy plan
    against the ``split_all`` plan of the *same* chain; the tuner's
    pick is recorded through a memory-only cache and reported in-band
    so the bench doubles as a tune_plan smoke."""
    import numpy as _np

    import repro.fuse as F
    from repro.sparse import Schedule
    from repro.tune import ScheduleCache

    sched = Schedule("eb", nnz_tile=256, group_size=32)
    cache = ScheduleCache(path=None)  # never touch the user's cache
    rows, wins = [], []

    # two-layer GCN chains over the synthetic suite
    sizes = ((256, 256), (512, 512)) if quick else \
        ((1024, 1024), (2048, 2048))
    mats = suite(sizes=sizes, densities=(0.01,), skews=(0.0, 1.5))
    c = 32 if quick else 64
    rng = _np.random.default_rng(0)
    for (m, n, dens, s), csr in mats:
        x = jnp.asarray(rng.normal(size=(m, c)), jnp.float32)
        w0 = jnp.asarray(rng.normal(size=(c, c)) * c ** -0.5, jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(c, c)) * c ** -0.5, jnp.float32)
        b0 = jnp.asarray(rng.normal(size=(c,)), jnp.float32)
        chain, params = F.gcn_chain(csr, (w0, w1), (b0, None),
                                    schedule=sched)
        fused, split = F.plan(chain), F.split_all(chain)
        t_fused = time_fn(lambda xx, p=fused, pr=params:
                          F.run_plan(p, xx, pr), x, warmup=1, iters=3)
        t_split = time_fn(lambda xx, p=split, pr=params:
                          F.run_plan(p, xx, pr), x, warmup=1, iters=3)
        res = F.tune_plan(chain, x, params, cache=cache, warmup=1, iters=2)
        wins.append(t_split / max(t_fused, 1e-12))
        rows.append((f"beyond/fusion_planner/gcn_m{m}_skew{s}",
                     t_fused * 1e6,
                     f"split_us={t_split * 1e6:.1f},"
                     f"launches={fused.n_launches},"
                     f"tuned={res.schedule.tag},"
                     f"fused_vs_split={wins[-1]:.3f}"))

    # MoE expert-GEMM chain (SiLU + per-expert bias on the output block)
    tile = 128
    t_tiles = 4 if quick else 16
    d = f = 128 if quick else 256
    e = 8
    x = jnp.asarray(rng.normal(size=(t_tiles * tile, d)), jnp.float32)
    te = jnp.asarray(rng.integers(0, e, size=(t_tiles,)), jnp.int32)
    w = jnp.asarray(rng.normal(size=(e, d, f)) * d ** -0.5, jnp.float32)
    b = jnp.asarray(rng.normal(size=(e, f)), jnp.float32)
    chain, params = F.moe_expert_chain(te, w, b, token_tile=tile)
    fused, split = F.plan(chain), F.split_all(chain)
    t_fused = time_fn(lambda xx: F.run_plan(fused, xx, params), x,
                      warmup=1, iters=3)
    t_split = time_fn(lambda xx: F.run_plan(split, xx, params), x,
                      warmup=1, iters=3)
    res = F.tune_plan(chain, x, params, cache=cache, warmup=1, iters=2)
    wins.append(t_split / max(t_fused, 1e-12))
    rows.append((f"beyond/fusion_planner/moe_t{t_tiles * tile}",
                 t_fused * 1e6,
                 f"split_us={t_split * 1e6:.1f},"
                 f"launches={fused.n_launches},tuned={res.schedule.tag},"
                 f"fused_vs_split={wins[-1]:.3f}"))

    rows.append(("beyond/fusion_planner_gap", 0.0,
                 f"fused_vs_split_geomean={geomean(wins):.3f}"))
    return rows


def selector_quality(quick=True):
    """Behavioral check of the data-aware selector (DA-SpMM-style): it
    must choose nnz-split + segment for skewed matrices (balance-bound)
    and be waste-aware for short-row regimes. Reports decisions + the
    waste the choice avoids, then the empirical tuned-vs-auto-vs-oracle
    gap (the autotuner's tracked win, ISSUE 2)."""
    from repro.core import Schedule, candidate_schedules, group_waste_fraction
    from repro.tune import ScheduleCache, measure_schedule, tune_schedule
    import numpy as _np

    mats = suite(sizes=((2048, 2048),), densities=(0.002, 0.01),
                 skews=(0.0, 2.0))
    n_dense = 4
    rows = []
    correct = 0
    for (m, n, d, s), csr in mats:
        stats = matrix_stats(csr)
        sel = select_schedule(stats, n_dense)
        lengths = _np.asarray(csr.row_lengths())
        expect_eb = stats["row_cv"] > 1.0
        ok = (sel.kernel == "eb") == expect_eb or not expect_eb
        correct += ok
        rows.append((f"beyond/selector/d{d}_skew{s}", 0.0,
                     f"picked={sel.kernel}/G{sel.group_size},"
                     f"row_cv={stats['row_cv']:.2f},"
                     f"waste32={group_waste_fraction(lengths, 32):.2f},"
                     f"wasteG={group_waste_fraction(lengths, sel.group_size):.2f},"
                     f"ok={ok}"))
    rows.append(("beyond/selector_quality", 0.0,
                 f"decision_accuracy={correct}/{len(mats)}"))

    # tuned vs auto vs measured oracle (memory-only cache: the benchmark
    # must not read or pollute the user's persistent cache)
    cache = ScheduleCache(path=None)
    gap_mats = mats if not quick else mats[:3]
    tuned_vs_auto, auto_vs_oracle, tuned_vs_oracle = [], [], []
    for (m, n, d, s), csr in gap_mats:
        res = tune_schedule(csr, n_dense, cache=cache, warmup=1, iters=3)
        auto = Schedule.auto(matrix_stats(csr), n_dense)
        t_auto = measure_schedule(csr, n_dense, auto, warmup=1,
                                  iters=3) * 1e6
        t_oracle = min([measure_schedule(csr, n_dense, sc, warmup=1, iters=2)
                        * 1e6 for sc in candidate_schedules(n_dense)]
                       + [res.us_per_call])
        tuned_vs_auto.append(t_auto / max(res.us_per_call, 1e-9))
        auto_vs_oracle.append(t_auto / max(t_oracle, 1e-9))
        tuned_vs_oracle.append(res.us_per_call / max(t_oracle, 1e-9))
        rows.append((f"beyond/tuner/d{d}_skew{s}", res.us_per_call,
                     f"tuned={res.schedule.kernel}/G{res.schedule.group_size},"
                     f"auto_us={t_auto:.1f},oracle_us={t_oracle:.1f},"
                     f"tuned_vs_auto={tuned_vs_auto[-1]:.3f}"))
    rows.append(("beyond/tuner_gap", 0.0,
                 f"tuned_vs_auto_geomean={geomean(tuned_vs_auto):.3f},"
                 f"auto_vs_oracle_geomean={geomean(auto_vs_oracle):.3f},"
                 f"tuned_vs_oracle_geomean={geomean(tuned_vs_oracle):.3f}"))
    return rows


def _dist_mesh():
    """The 1-D reduction mesh over whatever devices exist: 8 forced host
    devices in the CI ``dist`` lane, 1 elsewhere (degenerate but valid —
    collectives compile away, win ratios sit at ~1.0)."""
    from repro.launch.mesh import make_reduction_mesh

    mesh = make_reduction_mesh()
    return mesh, int(mesh.shape["shards"])


def dist_attention_gap(quick=True):
    """Tuned-vs-fixed collective mode for distributed fused attention
    (DESIGN.md §12): time ``dist_attention_shard_map`` under every
    feasible wire mode (row / nnz_ar / nnz_rs) on the real mesh, report
    the fixed atomic-style psum ('nnz_ar') vs the measured best — the
    best is the measured minimum of a pool containing the fixed mode, so
    the geomean is >= 1.0 by construction — and, on a >1-device mesh,
    the compiled nnz_rs collective bytes against the roofline
    prediction (acceptance: within 10%)."""
    from repro.roofline.analysis import (collective_bytes,
                                         predict_attention_collective_bytes)
    from repro.sparse import Schedule
    from repro.sparse.distributed import (dist_attention_shard_map,
                                          partition_nnz_coo,
                                          partition_rows_coo)
    from repro.sparse.random import power_law_csr, random_csr

    mesh, axis_size = _dist_mesh()
    n = 128 if quick else 256
    d = dv = 16 if quick else 32
    h = 2
    sched = Schedule("eb", nnz_tile=64, group_size=8)
    mats = [("powerlaw", power_law_csr(n, n, avg_degree=6.0, alpha=1.6,
                                       seed=0)),
            ("uniform", random_csr(n, n, density=0.05, seed=1))]
    modes = ["nnz_ar"]
    if n % axis_size == 0:
        modes += ["nnz_rs", "row"]

    q = jax.random.normal(jax.random.PRNGKey(0), (h, n, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (h, n, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (h, n, dv))

    rows_out, wins = [], []
    bytes_row = None
    for name, csr in mats:
        timings = {}
        for mode in modes:
            part = partition_rows_coo if mode == "row" else partition_nnz_coo
            rows, cols, _, _ = part(csr, axis_size, sched.nnz_tile,
                                    pattern_only=True, phantom_row=True)
            fn = jax.jit(lambda r, c, qq, kk, vv, _m=mode: (
                dist_attention_shard_map(r, c, qq, kk, vv, n_rows=n,
                                         mesh=mesh, axis="shards",
                                         mode=_m, schedule=sched)))
            timings[mode] = time_fn(fn, rows, cols, q, k, v,
                                    warmup=1, iters=3) * 1e6
            if (bytes_row is None and mode == "nnz_rs" and axis_size > 1):
                compiled = fn.lower(rows, cols, q, k, v).compile()
                colls = collective_bytes(compiled.as_text())
                meas = sum(rec["bytes"] for rec in colls.values())
                pred = predict_attention_collective_bytes(
                    "nnz_rs", n_heads=h, n_rows=n, dv_pad=dv,
                    axis_size=axis_size)
                bytes_row = ("beyond/dist_attention_bytes", 0.0,
                             f"mode=nnz_rs,coll_bytes_meas={meas},"
                             f"coll_bytes_pred={pred},"
                             f"meas_vs_pred={meas / max(pred, 1):.3f}")
        best_mode = min(timings, key=timings.get)
        wins.append(timings["nnz_ar"] / max(timings[best_mode], 1e-9))
        detail = ",".join(f"{m}_us={timings[m]:.1f}" for m in modes)
        rows_out.append((f"beyond/dist_attention/{name}",
                         timings[best_mode],
                         f"best={best_mode},axis={axis_size},{detail},"
                         f"tuned_vs_fixed={wins[-1]:.3f}"))
    if bytes_row is not None:
        rows_out.append(bytes_row)
    rows_out.append(("beyond/dist_attention_gap", 0.0,
                     f"tuned_vs_fixed_geomean={geomean(wins):.3f}"))
    return rows_out


def dist_moe_gap(quick=True):
    """Tuned-vs-fixed expert-parallel writeback collective (DESIGN.md
    §12): ``moe_tune_collective`` measures ``apply_moe`` end to end
    under psum ('nnz_ar', the fixed historical mode) and psum_scatter
    ('nnz_rs') on the real mesh and picks the winner; the win ratio is
    fixed/best >= 1.0 by construction.  On a >1-device mesh the
    compiled nnz_rs collective bytes are checked against the roofline
    prediction."""
    from repro.models.moe import (ShardingCtx, default_dispatch,
                                  moe_tune_collective)
    from repro.roofline.analysis import (collective_bytes,
                                         predict_collective_bytes)
    from repro.tune import ScheduleCache
    from repro.tune.moe import moe_schedule_key

    n_dev = jax.device_count()
    mesh = jax.make_mesh((n_dev,), ("model",))
    ctx = ShardingCtx(mesh=mesh, data_axes=(), model_axis="model")
    cfg = smoke_config(ARCHS["qwen3-moe-235b-a22b"]).scaled(
        d_model=64, moe_d_ff=64 if quick else 128, n_experts=8,
        experts_per_token=2)
    t_tokens = 256 if quick else 1024
    p = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (t_tokens, cfg.d_model))

    cache = ScheduleCache(path=None)  # never touch the user's cache
    res = moe_tune_collective(cfg, p, x, ctx, cache=cache,
                              warmup=1, iters=3)
    base = default_dispatch(cfg)
    fixed_key = moe_schedule_key(base.replace(collective="nnz_ar"))
    t_fixed = res.measured[fixed_key]
    win = t_fixed / max(res.us_per_call, 1e-9)
    rows = [(f"beyond/dist_moe/{key.rsplit('w[', 1)[-1].rstrip(']')}",
             us, f"axis={n_dev}")
            for key, us in sorted(res.measured.items())]
    if n_dev > 1:
        sched = base.replace(collective="nnz_rs")
        fn = jax.jit(lambda xx: apply_moe(cfg, p, xx, ctx,
                                          dispatch=sched)[0])
        compiled = fn.lower(x).compile()
        colls = collective_bytes(compiled.as_text())
        meas = sum(rec["bytes"] for rec in colls.values())
        pred = predict_collective_bytes("nnz_rs", (t_tokens, cfg.d_model),
                                        axis_size=n_dev)
        rows.append(("beyond/dist_moe_bytes", 0.0,
                     f"mode=nnz_rs,coll_bytes_meas={meas},"
                     f"coll_bytes_pred={pred},"
                     f"meas_vs_pred={meas / max(pred, 1):.3f}"))
    rows.append(("beyond/dist_moe_gap", 0.0,
                 f"tuned={res.schedule.collective},"
                 f"fixed_us={t_fixed:.1f},"
                 f"tuned_vs_fixed_geomean={win:.3f}"))
    return rows


def lowprec_spmm(quick=True):
    """Low-precision value storage as a schedule axis (ISSUE 9,
    DESIGN.md §13): f32 vs bf16 vs int8 SpMM on the same patterns.

    Two numbers per (matrix, dtype), from the same jitted schedule
    analogue the tuner measures (narrow arrays genuinely fed):

    * ``us`` — XLA-CPU wall clock.  Honest but a poor proxy for the
      paper's hardware: this backend converts bf16 through a scalar
      path, so the bandwidth saving does not reach the clock here.
    * modeled traffic bytes (``roofline.predict_spmm_traffic_bytes``)
      — the gather-dominated stream model a bandwidth-bound backend
      realizes; the headline ``modeled_speedup`` geomeans come from it
      (bf16 ~2x fewer bytes than f32 on these shapes).

    The tuner's parity gate is reported alongside (``err``): every
    narrow row shown is within the 5% default ``error_budget``.
    """
    from repro.core import Schedule
    from repro.roofline.analysis import predict_spmm_traffic_bytes
    from repro.sparse.random import power_law_csr, random_csr
    from repro.tune.search import _dtype_parity_error

    n = 4096 if quick else 16384
    C = 64
    mats = [("uniform", random_csr(n, n, density=0.004, seed=0)),
            ("powerlaw", power_law_csr(n, n, avg_degree=16.0, alpha=1.8,
                                       seed=1))]
    base = Schedule("eb", nnz_tile=512, group_size=32,
                    strategy="accumulate", col_tile=C)

    rows = []
    ratios = {"bfloat16": {"us": [], "bytes": []},
              "int8": {"us": [], "bytes": []}}
    for name, csr in mats:
        per = {}
        for vd in (None, "bfloat16", "int8"):
            fn, args = make_runner(csr, C, base.replace(value_dtype=vd))
            lanes = args[0].shape[0]
            t = time_fn(fn, *args, warmup=1, iters=3) * 1e6
            by = predict_spmm_traffic_bytes(
                lanes, csr.shape[0], C, value_dtype=vd,
                scales_rows=csr.shape[0] if vd == "int8" else 0)
            per[vd] = (t, by)
        t32, b32 = per[None]
        rows.append((f"beyond/lowprec/{name}/f32", t32,
                     f"modeled_mb={b32 / 1e6:.1f},nnz={csr.nnz}"))
        for vd in ("bfloat16", "int8"):
            t, by = per[vd]
            err = _dtype_parity_error(csr, C, vd)
            ratios[vd]["us"].append(t32 / max(t, 1e-9))
            ratios[vd]["bytes"].append(b32 / by)
            rows.append((f"beyond/lowprec/{name}/{vd}", t,
                         f"modeled_mb={by / 1e6:.1f},"
                         f"modeled_speedup={b32 / by:.2f},"
                         f"us_vs_f32={t32 / max(t, 1e-9):.2f},"
                         f"err={err:.4f}"))
    rows.append((
        "beyond/lowprec_spmm", 0.0,
        f"modeled_speedup_geomean_bf16={geomean(ratios['bfloat16']['bytes']):.2f},"
        f"modeled_speedup_geomean_int8={geomean(ratios['int8']['bytes']):.2f},"
        f"us_geomean_bf16={geomean(ratios['bfloat16']['us']):.2f},"
        f"us_geomean_int8={geomean(ratios['int8']['us']):.2f}"))
    return rows


def skew_tuner_gap(quick=True):
    """Skew-aware two-level scheduling on power-law graphs (ISSUE 7).

    For each power-law / graph-pattern matrix, ``tune_schedule`` searches
    the full space *including* the split/merge thresholds (DESIGN.md
    §11) against a memory-only cache; the best *static* point is the
    fastest schedule in the same run's measured pool that carries no
    skew thresholds.  Tuned and static timings come from one ``_Memo``
    sweep, so the win ratio compares like with like — and since the
    tuner picks the measured minimum, the geomean is >= 1.0 whenever a
    skew point wins anywhere and == 1.0 where the plain layout is
    already optimal (the 'roadnet' control row should sit at ~1.0).
    """
    import re as _re

    from repro.sparse.random import graph_pattern_csr, power_law_csr
    from repro.tune import ScheduleCache, tune_schedule

    n = 1024 if quick else 4096
    n_dense = 4
    mats = [("powerlaw", power_law_csr(n, n, avg_degree=8.0, alpha=1.8,
                                       seed=0))]
    mats += [(p, graph_pattern_csr(p, n, seed=1))
             for p in ("web", "social", "roadnet")]

    cache = ScheduleCache(path=None)  # never touch the user's cache
    rows, wins = [], []
    for name, csr in mats:
        res = tune_schedule(csr, n_dense, cache=cache, warmup=1, iters=3)
        # skew points carry ':s<split>:m<merge>' in their schedule_key
        # (':segment' has no digit after ':s', so it doesn't match)
        static = {k: v for k, v in res.measured.items()
                  if not _re.search(r":s\d", k)}
        t_static = min(static.values())
        wins.append(t_static / max(res.us_per_call, 1e-9))
        s = res.schedule
        skew = (f"s{s.split_threshold}/m{s.merge_threshold}"
                if s.is_skew else "plain")
        rows.append((f"beyond/skew/{name}", res.us_per_call,
                     f"tuned={s.kernel}/G{s.group_size}/{skew},"
                     f"static_us={t_static:.1f},"
                     f"tuned_vs_static={wins[-1]:.3f},nnz={csr.nnz}"))
    rows.append(("beyond/skew_gap", 0.0,
                 f"tuned_vs_static_geomean={geomean(wins):.3f}"))
    return rows


def joint_dist_gap(quick=True):
    """Joint collective × value-dtype search for distributed SpMM (ISSUE
    10, DESIGN.md §14): one ``tune_dist_spmm`` run searches local tiling
    × wire mode × storage width in a *single* objective.  The fixed
    baseline is the fastest f32 point in the same run's measured pool
    (keys without a ``:v[..]`` fragment) — what two sequential
    single-axis searches could at best deliver for the wire mode alone —
    so the win ratio (fixed/best) is >= 1.0 by construction: the joint
    winner is the measured minimum of a superset."""
    from repro.sparse.random import power_law_csr, random_csr
    from repro.tune import ScheduleCache, tune_dist_spmm

    mesh, axis_size = _dist_mesh()
    n = 512 if quick else 2048
    n_dense = 4
    mats = [("uniform", random_csr(n, n, density=0.01, seed=0)),
            ("powerlaw", power_law_csr(n, n, avg_degree=8.0, alpha=1.6,
                                       seed=1))]

    cache = ScheduleCache(path=None)  # never touch the user's cache
    rows, wins = [], []
    for name, csr in mats:
        res = tune_dist_spmm(csr, n_dense, mesh=mesh, axis="shards",
                             cache=cache, warmup=1, iters=3)
        f32 = {k: v for k, v in res.measured.items() if ":v[" not in k}
        t_fixed = min(f32.values())
        wins.append(t_fixed / max(res.us_per_call, 1e-9))
        s = res.schedule
        rows.append((f"beyond/joint_dist/{name}", res.us_per_call,
                     f"tuned={s.collective}/v{s.value_dtype or 'f32'},"
                     f"axis={axis_size},f32_best_us={t_fixed:.1f},"
                     f"n_measured={len(res.measured)},"
                     f"tuned_vs_fixed={wins[-1]:.3f}"))
    rows.append(("beyond/joint_dist_gap", 0.0,
                 f"tuned_vs_fixed_geomean={geomean(wins):.3f}"))
    return rows


def fuse_boundary_gap(quick=True):
    """Per-boundary fuse decisions on a 3-boundary chain (ISSUE 10,
    DESIGN.md §14): ``tune_plan`` on a 4-node GCN chain seeds the two
    all-or-nothing plans (greedy-fused, fully-split) and then hillclimbs
    *individual* boundary flips — a mixed tag like ``FSS`` is reachable
    only through the per-boundary search.  The fixed baseline is the
    faster all-or-nothing seed from the same measured pool, so the win
    ratio is >= 1.0 by construction."""
    import numpy as np

    from repro.core import Schedule
    from repro.fuse import gcn_chain, split_all, tune_plan
    from repro.fuse.planner import plan
    from repro.sparse.random import random_csr
    from repro.tune import ScheduleCache

    rng = np.random.default_rng(0)
    n = 64 if quick else 256
    d = 8 if quick else 16
    adj = random_csr(n, n, density=0.1, seed=0)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w0 = jnp.asarray(rng.normal(size=(d, d)) * 0.3, jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(d, d)) * 0.3, jnp.float32)
    b0 = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    b1 = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    sched = Schedule("eb", nnz_tile=128, group_size=8)
    chain, params = gcn_chain(adj, (w0, w1), (b0, b1),
                              final_activation="relu", schedule=sched)

    cache = ScheduleCache(path=None)  # never touch the user's cache
    res = tune_plan(chain, x, params, cache=cache, warmup=1, iters=3)
    seeds = {plan(chain).decision.tag, split_all(chain).decision.tag}
    t_fixed = min(res.measured[t] for t in seeds)
    win = t_fixed / max(res.us_per_call, 1e-9)
    rows = [(f"beyond/fuse_boundary/{tag}", us,
             "seed" if tag in seeds else "flip")
            for tag, us in sorted(res.measured.items())]
    rows.append(("beyond/fuse_boundary_gap", 0.0,
                 f"tuned={res.schedule.tag},fixed_us={t_fixed:.1f},"
                 f"n_measured={len(res.measured)},"
                 f"tuned_vs_fixed_geomean={win:.3f}"))
    return rows
