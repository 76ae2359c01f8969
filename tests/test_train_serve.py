"""End-to-end trainer + serving engine tests on a tiny model (CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.data.synthetic import ShardedTokenStream
from repro.models.registry import get_model
from repro.serve.engine import Request, ServeEngine
from repro.train.optimizer import AdamW, constant_schedule
from repro.train.train_step import init_state, make_train_step
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def tiny():
    cfg = smoke_config(ARCHS["qwen2-7b"])
    api = get_model(cfg)
    return cfg, api


def test_loss_decreases(tiny, tmp_path):
    cfg, api = tiny
    opt = AdamW(lr=constant_schedule(3e-3), weight_decay=0.0)
    data = ShardedTokenStream(cfg.vocab_size, 32, 8, seed=0)
    tr = Trainer(api, opt, iter(data), ckpt_dir=tmp_path,
                 tcfg=TrainerConfig(total_steps=30, ckpt_every=10,
                                    log_every=100))
    state = tr.init_or_restore(jax.random.PRNGKey(0))
    state = tr.run(state)
    losses = tr.losses()
    assert losses[-5:].mean() < losses[:5].mean() - 0.1, losses
    assert tr.ckpt.all_steps()  # checkpoints written


def test_checkpoint_restart_continuity(tiny, tmp_path):
    cfg, api = tiny
    opt = AdamW(lr=constant_schedule(1e-3), weight_decay=0.0)
    data = ShardedTokenStream(cfg.vocab_size, 32, 8, seed=0)
    tr = Trainer(api, opt, iter(data), ckpt_dir=tmp_path,
                 tcfg=TrainerConfig(total_steps=10, ckpt_every=5,
                                    log_every=100))
    state = tr.init_or_restore(jax.random.PRNGKey(0))
    tr.run(state)
    # second trainer restores from step 10 and continues
    data2 = ShardedTokenStream(cfg.vocab_size, 32, 8, seed=0)
    tr2 = Trainer(api, opt, iter(data2), ckpt_dir=tmp_path,
                  tcfg=TrainerConfig(total_steps=12, ckpt_every=5,
                                     log_every=100))
    state2 = tr2.init_or_restore(jax.random.PRNGKey(1))
    assert int(state2.opt.step) == 10
    state2 = tr2.run(state2)
    assert int(state2.opt.step) == 12


def test_microbatch_equivalence(tiny):
    """grad accumulation over 2 microbatches == full-batch step (same loss
    trajectory within fp tolerance)."""
    cfg, api = tiny
    opt = AdamW(lr=constant_schedule(1e-3), weight_decay=0.0,
                clip_norm=None)
    state = init_state(api, opt, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                          cfg.vocab_size, jnp.int32)}
    s1 = jax.jit(make_train_step(api, opt))(state, batch)[0]
    s2 = jax.jit(make_train_step(api, opt, microbatches=2))(state, batch)[0]
    w1 = np.asarray(jax.tree.leaves(s1.params)[0], np.float32)
    w2 = np.asarray(jax.tree.leaves(s2.params)[0], np.float32)
    np.testing.assert_allclose(w1, w2, rtol=5e-4, atol=5e-5)


def test_grad_compression_step_runs(tiny):
    cfg, api = tiny
    opt = AdamW(lr=constant_schedule(1e-3))
    state = init_state(api, opt, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                          cfg.vocab_size, jnp.int32)}
    step = jax.jit(make_train_step(api, opt, grad_compression="int8"))
    state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_serve_engine_completes(tiny):
    cfg, api = tiny
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, params, slots=2, max_len=64)
    rng = np.random.default_rng(0)
    for rid in range(3):
        eng.submit(Request(rid=rid,
                           prompt=rng.integers(0, cfg.vocab_size, 8,
                                               dtype=np.int32),
                           max_new_tokens=4))
    results = eng.run_to_completion(max_steps=50)
    assert sorted(results) == [0, 1, 2]
    assert all(len(v) == 4 for v in results.values())
    assert all(0 <= t < cfg.vocab_size for v in results.values() for t in v)


def test_moe_pallas_dispatch_matches_einsum():
    """The Pallas grouped-matmul MoE path must equal the einsum path."""
    from repro.models.moe import apply_moe, init_moe

    cfg = smoke_config(ARCHS["qwen3-moe-235b-a22b"])
    p = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    out_e, aux_e = apply_moe(cfg, p, x, None)
    cfg_p = cfg.scaled(moe_pallas_dispatch=True)
    out_p, aux_p = apply_moe(cfg_p, p, x, None)
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_p),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_e), float(aux_p), rtol=1e-5)
