"""Distributed tests: run in a subprocess with 8 forced host devices so the
main pytest process keeps its single-device view.  The forced-device
environment (and the device-count assertion) lives in
``conftest.run_distributed`` — snippets here contain only the test.
"""
import pytest

from conftest import run_distributed as _run


DISTRIBUTED_SPMM = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.sparse import random_csr, GroupedCOO
from repro.sparse.distributed import spmm_shard_map
from repro.kernels import ref

mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
n_rows, n_cols = 64, 48
csr = random_csr(n_rows, n_cols, density=0.05, seed=0)
g = GroupedCOO.fromcsr(csr, 8)  # nnz padded to a multiple of 8
b = jax.random.normal(jax.random.PRNGKey(0), (n_cols, 16))
want = np.asarray(ref.spmm_coo_ref(g.rows, g.cols, g.vals, b, n_rows))
for mode in ("nnz_ar", "nnz_rs"):
    got = np.asarray(spmm_shard_map(g.rows, g.cols, g.vals, b,
                                    n_rows=n_rows, mesh=mesh, axis="data",
                                    mode=mode))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    print(mode, "OK")

# row mode: pre-partition rows locally
rows_per = n_rows // 8
import numpy as onp
rows_np = onp.asarray(g.rows); cols_np = onp.asarray(g.cols); vals_np = onp.asarray(g.vals)
buckets = [[] for _ in range(8)]
for r, c, v in zip(rows_np, cols_np, vals_np):
    buckets[min(int(r) // rows_per, 7)].append((int(r) % rows_per if r < 8*rows_per else r - 7*rows_per, c, v))
width = max(len(bk) for bk in buckets)
lr = onp.zeros((8, width), onp.int32); lc = onp.zeros((8, width), onp.int32)
lv = onp.zeros((8, width), onp.float32)
for i, bk in enumerate(buckets):
    for j, (r, c, v) in enumerate(bk):
        lr[i, j], lc[i, j], lv[i, j] = r, c, v
got = np.asarray(spmm_shard_map(jnp.asarray(lr.reshape(-1)),
                                jnp.asarray(lc.reshape(-1)),
                                jnp.asarray(lv.reshape(-1)), b,
                                n_rows=n_rows, mesh=mesh, axis="data",
                                mode="row"))
np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
print("row OK")
"""


MOE_EP = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ARCHS, smoke_config
from repro.models.moe import apply_moe, init_moe, ShardingCtx

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
# capacity_factor large enough that no token is dropped in either layout,
# so expert parallelism must match the single-shard result exactly.
cfg = smoke_config(ARCHS["qwen3-moe-235b-a22b"]).scaled(capacity_factor=4.0)
p = init_moe(cfg, jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
ref_out, ref_aux = apply_moe(cfg, p, x, None)
ctx = ShardingCtx(mesh=mesh, data_axes=("data",), model_axis="model")
with mesh:
    out, aux = jax.jit(lambda p, x: apply_moe(cfg, p, x, ctx))(p, x)
close = np.isclose(np.asarray(out), np.asarray(ref_out), rtol=1e-3,
                   atol=1e-3).all(axis=-1).mean()
assert close > 0.999, close
print("moe EP OK, agreement", close)
"""


SEQ_SHARDED_DECODE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import ARCHS, smoke_config
from repro.models.registry import get_model
from repro.distributed.sharding import cache_shardings, param_shardings

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = smoke_config(ARCHS["qwen2-7b"]).scaled(n_kv_heads=2)
api = get_model(cfg)
params = api.init(jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 15), 0,
                                      cfg.vocab_size, jnp.int32)}
logits_ref, cache = api.prefill(params, batch, 32)
tok = jnp.argmax(logits_ref, -1).astype(jnp.int32)
want, _ = api.decode_step(params, cache, tok)

pshard = param_shardings(mesh, jax.eval_shape(api.init, jax.random.PRNGKey(0)))
csh = cache_shardings(mesh, cfg, jax.eval_shape(lambda: cache))
params_s = jax.device_put(params, pshard)
cache_s = jax.device_put(cache, csh)
with mesh:
    got, new_cache = jax.jit(api.decode_step)(params_s, cache_s, tok)
np.testing.assert_allclose(np.asarray(got, np.float32),
                           np.asarray(want, np.float32), rtol=2e-3, atol=2e-3)
print("seq-sharded decode OK; cache seq spec:",
      new_cache["k"].sharding.spec)
"""


@pytest.mark.slow
def test_distributed_spmm_modes():
    out = _run(DISTRIBUTED_SPMM)
    assert "row OK" in out


@pytest.mark.slow
def test_moe_expert_parallel_matches_single():
    out = _run(MOE_EP)
    assert "moe EP OK" in out


@pytest.mark.slow
def test_seq_sharded_kv_decode_matches_single():
    out = _run(SEQ_SHARDED_DECODE)
    assert "seq-sharded decode OK" in out


SEQ_PARALLEL_ATTENTION = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import ARCHS, smoke_config
from repro.models.registry import get_model

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = smoke_config(ARCHS["qwen2-7b"])
api = get_model(cfg)
params = api.init(jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                      cfg.vocab_size, jnp.int32)}
want = float(api.loss(params, batch))
gw = jax.grad(api.loss)(params, batch)

cfg_sp = cfg.scaled(seq_parallel_attn=True)
api_sp = get_model(cfg_sp)
with mesh:
    got = float(jax.jit(api_sp.loss)(params, batch))
    gg = jax.jit(jax.grad(api_sp.loss))(params, batch)
assert abs(got - want) < 2e-3, (got, want)
for a, b in zip(jax.tree.leaves(gw), jax.tree.leaves(gg)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-2,
                               atol=2e-3)
print("seq-parallel attention OK, loss", got)
"""


@pytest.mark.slow
def test_seq_parallel_attention_matches_single():
    out = _run(SEQ_PARALLEL_ATTENTION)
    assert "seq-parallel attention OK" in out


ELASTIC_REMESH = """
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import ARCHS, smoke_config
from repro.models.registry import get_model
from repro.checkpoint.manager import CheckpointManager
from repro.train.optimizer import AdamW, constant_schedule
from repro.train.train_step import init_state, make_train_step
from repro.distributed.fault_tolerance import plan_remesh

cfg = smoke_config(ARCHS["qwen2-7b"])
api = get_model(cfg)
opt = AdamW(lr=constant_schedule(1e-3))
step = jax.jit(make_train_step(api, opt))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                      cfg.vocab_size, jnp.int32)}

# phase 1: train on a (4, 2) mesh, checkpoint
mesh1 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
state = init_state(api, opt, jax.random.PRNGKey(0))
state = jax.device_put(state, NamedSharding(mesh1, P()))
with mesh1:
    for _ in range(3):
        state, m = step(state, batch)
loss_before = float(m["loss"])
tmp = tempfile.mkdtemp()
mgr = CheckpointManager(tmp, async_save=False)
mgr.save(3, state)

# phase 2: "lose" half the fleet -> re-mesh to (2, 2) on 4 devices and
# restore the same checkpoint under the new topology
shape = plan_remesh(n_healthy_hosts=1, chips_per_host=4, model_parallel=2)
assert shape == (2, 2), shape
devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
mesh2 = jax.sharding.Mesh(devs, ("data", "model"))
restored, step_no = mgr.restore(
    jax.tree.map(jnp.zeros_like, state),
    shardings=jax.tree.map(lambda _: NamedSharding(mesh2, P()), state))
assert step_no == 3
with mesh2:
    restored, m2 = step(restored, batch)
assert int(restored.opt.step) == 4
# same params + same batch -> the post-restore loss must equal a
# continuation on the original mesh
with mesh1:
    cont, m1 = step(state, batch)
assert abs(float(m2["loss"]) - float(m1["loss"])) < 1e-4, (
    float(m2["loss"]), float(m1["loss"]))
print("elastic remesh OK: step", step_no, "->", int(restored.opt.step),
      "loss", float(m2["loss"]))
"""


@pytest.mark.slow
def test_elastic_remesh_checkpoint_restore():
    out = _run(ELASTIC_REMESH)
    assert "elastic remesh OK" in out
