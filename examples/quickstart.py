"""Quickstart: the Sgap segment-group SpMM through the unified Schedule API.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro.sparse import (Schedule, matrix_stats, random_csr,
                          register_strategy, segment_reduce, spmm)

# A skewed sparse matrix (a few very long rows) — the regime where the
# paper's flexible reduction wins.
A = random_csr(512, 512, density=0.02, skew=1.5, seed=0)
B = jax.random.normal(jax.random.PRNGKey(0), (512, 8))

# 1. schedule='auto' runs the data-aware selector (paper Table 5 made a
#    library default) and checks against the pure-jnp oracle.
stats = matrix_stats(A)
print(f"matrix: {stats['nnz']} nnz, row CV {stats['row_cv']:.2f}")
print(f"auto schedule: {Schedule.auto(stats, B.shape[1])}")
out = spmm(A, B, schedule="auto")
ref = spmm(A, B, impl="ref")
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                           atol=1e-4)
print("auto schedule matches oracle ✓")

# 2. The four DA-SpMM points are named schedules; explicit Schedule objects
#    expose every tile / group-size / strategy knob.
for name in ("EB+PR", "EB+SR", "RB+PR", "RB+SR"):
    out_n = spmm(A, B, schedule=name)
    np.testing.assert_allclose(np.asarray(out_n), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    print(f"{name}: OK")
for r in (8, 32):
    s = Schedule("eb", nnz_tile=256, col_tile=8, group_size=r,
                 strategy="segment")
    out_r = spmm(A, B, schedule=s)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    print(f"group size r={r}: OK")

# 3. User-defined reduction strategy (paper challenge 2): register a pure-
#    JAX spec + in-kernel realization once; every op dispatches through it.
def _spec(partials, seg_ids, num_segments, group_size):
    onehot = (seg_ids[:, None]
              == jnp.arange(num_segments)[None, :]).astype(partials.dtype)
    return jnp.einsum("ts,tc->sc", onehot, partials)


def _pallas(rows, partial, out_ref, group_size):
    s = out_ref.shape[0]
    onehot = (rows[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (rows.shape[0], s), 1)).astype(partial.dtype)
    out_ref[...] += jnp.dot(onehot.T, partial,
                            preferred_element_type=jnp.float32)


register_strategy("onehot-tile", _spec, _pallas, overwrite=True)
seg = jnp.asarray(np.sort(np.random.default_rng(0).integers(0, 40, 200)),
                  jnp.int32)
data = jax.random.normal(jax.random.PRNGKey(1), (200, 8))
got = segment_reduce(seg, data, 40,
                     schedule=Schedule("eb", nnz_tile=64, group_size=32,
                                       strategy="onehot-tile"))
want = jax.ops.segment_sum(data, seg, num_segments=40)
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                           atol=1e-4)
print("custom strategy through the kernel: OK")

# 4. Generalized monoids + fused epilogues (DESIGN.md §8): the same
#    group machinery reduces with max (graph pooling), and a GCN layer's
#    act(A@XW + b) runs as ONE kernel via the schedule epilogue.
got_max = segment_reduce(seg, data, 40, op="max")
np.testing.assert_allclose(
    np.asarray(got_max),
    np.asarray(jax.ops.segment_max(data, seg, num_segments=40)),
    rtol=1e-4, atol=1e-4)
print("segment_reduce(op='max') through the registry: OK")

from repro.models.layers import gcn_layer  # noqa: E402

w = jax.random.normal(jax.random.PRNGKey(2), (512, 16)) * 0.1
bias = jax.random.normal(jax.random.PRNGKey(3), (16,))
fused = gcn_layer(A, jnp.eye(512), w, bias, activation="relu",
                  schedule="auto")
np.testing.assert_allclose(
    np.asarray(fused),
    np.asarray(jax.nn.relu(spmm(A, w, impl="ref") + bias[None, :])),
    rtol=1e-4, atol=1e-4)
print("fused GCN layer (bias+relu epilogue, one kernel): OK")

# 5. The fusion planner (DESIGN.md §10): describe a whole model fragment
#    as a chain of {sparse op, monoid, epilogue} nodes and let the
#    planner decide, per boundary, what rides which kernel launch.  The
#    two-layer GCN chain (spmm -> relu+bias -> spmm) plans to TWO Pallas
#    launches: each ewise node folds into its producing SpMM's epilogue.
import repro.fuse as fuse  # noqa: E402

w1 = jax.random.normal(jax.random.PRNGKey(4), (16, 8)) * 0.1
chain, params = fuse.gcn_chain(A, (w, w1), (bias, None), schedule="EB+PR")
plan = fuse.plan(chain)
print("GCN chain plan:", plan.decision.tag,
      f"({plan.n_launches} Pallas launches)")
assert plan.n_launches <= 2
for boundary, reason in enumerate(plan.reasons):
    if reason:
        print(f"  boundary {boundary} split: {reason}")

x = jnp.eye(512)
fused2 = fuse.run_plan(plan, x, params)
np.testing.assert_allclose(
    np.asarray(fused2),
    np.asarray(fuse.run_chain_ref(chain, x, params)),
    rtol=1e-4, atol=1e-4)
print("planned 2-layer GCN matches the unfused spec: OK")

# Fuse-vs-split is also a *measured* choice: tune_plan times both and
# records the winning FuseDecision in the schedule cache (fuse: keys),
# so the next call replays it with zero measurements.
from repro.tune import ScheduleCache  # noqa: E402

cache = ScheduleCache(path=None)  # demo: memory-only
res = fuse.tune_plan(chain, x, params, cache=cache, warmup=0, iters=1)
print("tuned decision:", res.schedule.tag, "| cached replay:",
      fuse.tune_plan(chain, x, params, cache=cache).from_cache)

# 6. Skew-aware two-level scheduling (DESIGN.md §11): on a power-law
#    graph, schedule='tune' searches split/merge thresholds that break
#    hub rows across dedicated 'parallel' groups and merge the 1-2 nnz
#    tail into shared ones — then replays the winner from cache.
from repro.sparse import power_law_csr  # noqa: E402
from repro.tune import tune_schedule  # noqa: E402

G = power_law_csr(1024, 1024, avg_degree=8.0, alpha=1.8, seed=0)
gstats = matrix_stats(G)
print(f"power-law graph: {gstats['nnz']} nnz, row CV "
      f"{gstats['row_cv']:.2f}, q50/q90/q99 row lengths "
      f"{[q for _, q in gstats['row_quantiles']]}")
res = tune_schedule(G, 4, cache=cache, warmup=1, iters=3)
print("tuned schedule:", res.schedule)
import re  # noqa: E402

best_static = min(us for key, us in res.measured.items()
                  if not re.search(r":s\d", key))  # non-skew points
print(f"tuned vs best static point: {best_static / res.us_per_call:.2f}x")
out_t = spmm(G, jax.random.normal(jax.random.PRNGKey(5), (1024, 4)),
             schedule=res.schedule)
print("skew-tuned spmm runs: OK | cached replay:",
      tune_schedule(G, 4, cache=cache).from_cache)

# 7. Mesh-elevated reduction strategies (DESIGN.md §12): the same
#    strategy question one level up — shards hold partial row sums and
#    the cross-shard combine is a collective ('row' = none, 'nnz_ar' =
#    psum, 'nnz_rs' = reduce-scatter).  schedule='tune' picks kernel
#    tiling AND wire mode in one pass and caches per mesh width.  Run
#    with XLA_FLAGS=--xla_force_host_platform_device_count=8 to see a
#    real 8-way mesh; on one device the mesh is degenerate but the path
#    is identical.
from repro.launch.mesh import make_reduction_mesh  # noqa: E402
from repro.sparse import dist_spmm  # noqa: E402
from repro.tune import tune_dist_spmm  # noqa: E402

mesh = make_reduction_mesh()
print(f"mesh: {mesh.shape}")
Bg = jax.random.normal(jax.random.PRNGKey(5), (1024, 4))
out_d = dist_spmm(G, Bg, mesh=mesh, axis="shards", schedule="tune",
                  cache=cache)
res_d = tune_dist_spmm(G, 4, mesh=mesh, axis="shards", cache=cache)
# the joint search may pick narrow value storage (§13) when it measures
# faster; the oracle reads the same storage
from repro.core.dtypes import operand_dtype, storage_dtype  # noqa: E402

vd = res_d.schedule.value_dtype
Gt = G if vd is None else G.astype(storage_dtype(vd))
Bt = Bg.astype(operand_dtype(vd)).astype(jnp.float32)
np.testing.assert_allclose(np.asarray(out_d, np.float32),
                           np.asarray(spmm(Gt, Bt, impl="ref")),
                           rtol=1e-4, atol=1e-4)
# ... and the f32 oracle within the tuner's parity budget (with slack)
want_d = np.asarray(spmm(G, Bg, impl="ref"))
rel_d = (np.linalg.norm(np.asarray(out_d, np.float32) - want_d)
         / np.linalg.norm(want_d))
assert rel_d <= 0.10, (vd, rel_d)
print("distributed spmm matches oracle: OK | tuned collective:",
      res_d.schedule.collective, "| cached replay:", res_d.from_cache)

# 8. Low-precision value storage (DESIGN.md §13): the stored dtype is
#    itself a schedule axis.  Values stream as bf16/fp16/fp8 — or int8
#    with per-row scales dequantized inside the reduction — while
#    accumulation stays f32.  schedule='tune' measures narrow variants
#    of the winning schedule and keeps one only when it is faster AND
#    inside a relative-error budget; on hosts without native fp8 the
#    fp8 dtypes degrade to bf16 with a warning instead of failing.
from repro.core import fp8_supported  # noqa: E402
from repro.sparse import quantize_csr  # noqa: E402

s16 = Schedule("eb", nnz_tile=256, col_tile=8, group_size=8,
               strategy="segment", value_dtype="bfloat16")
out16 = spmm(A, B, schedule=s16)
err16 = float(jnp.linalg.norm(out16 - ref) / jnp.linalg.norm(ref))
print(f"bf16 storage, f32 accumulation: rel err {err16:.1e}")

qA = quantize_csr(A)  # int8 values + per-row f32 scales
qerr = float(np.abs(np.asarray(qA.dequantize().vals)
                    - np.asarray(A.vals)).max())
print(f"int8 per-row quantization round-trip: max abs err {qerr:.1e}")

cache8 = ScheduleCache(path=None)
res8 = tune_schedule(A, 8, cache=cache8, warmup=0, iters=1,
                     value_dtypes=("bfloat16", "int8"))
print("tuned with dtype axis:", res8.schedule.value_dtype or "float32",
      "| fp8 native here:", fp8_supported())

# 9. Joint axis search (DESIGN.md §14): every tuner is a thin wrapper
#    over ONE driver composing Axis objects, so searches span axes
#    jointly.  tune_dist_spmm searches local tiling x collective wire
#    mode x value dtype in a single objective — a narrow dtype that
#    only pays off under reduce-scatter (or vice versa) is reachable,
#    where two sequential single-axis searches would each lock in the
#    other knob's default.  value_dtypes=() reduces to the §12
#    single-axis search; the winner replays measurement-free.
res_j = tune_dist_spmm(G, 4, mesh=mesh, axis="shards",
                       cache=ScheduleCache(path=None), warmup=0, iters=1)
sj = res_j.schedule
print(f"joint collective x dtype search: collective={sj.collective}",
      f"| dtype={sj.value_dtype or 'float32'}",
      f"| points measured={res_j.n_measurements}")
print("done")
