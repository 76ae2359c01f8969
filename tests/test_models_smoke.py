"""Per-architecture smoke tests: reduced same-family config, one forward +
train-grad step, one prefill + decode step. Asserts shapes + finiteness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, smoke_config
from repro.models.registry import get_model

B, S = 2, 32
MAXLEN = 48


def _batch(cfg, key=jax.random.PRNGKey(0)):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size,
                                          jnp.int32)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            key, (B, cfg.n_vision_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        batch["encoder_embeds"] = jax.random.normal(
            key, (B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return batch


@pytest.fixture(scope="module")
def built():
    cache = {}

    def _get(name):
        if name not in cache:
            cfg = smoke_config(ARCHS[name])
            api = get_model(cfg)
            params = api.init(jax.random.PRNGKey(42))
            cache[name] = (cfg, api, params)
        return cache[name]

    return _get


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_train_step_finite(built, name):
    cfg, api, params = built(name)
    batch = _batch(cfg)
    loss, grads = jax.value_and_grad(api.loss)(params, batch)
    assert np.isfinite(float(loss)), f"{name}: loss not finite"
    leaves = jax.tree.leaves(grads)
    assert leaves, f"{name}: no grads"
    for g in leaves:
        assert np.all(np.isfinite(np.asarray(g, np.float32))), (
            f"{name}: non-finite grad")


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_prefill_decode_shapes(built, name):
    cfg, api, params = built(name)
    batch = _batch(cfg)
    logits, cache = api.prefill(params, batch, MAXLEN)
    assert logits.shape == (B, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache2 = api.decode_step(params, cache, nxt)
    assert logits2.shape == (B, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits2, np.float32)))
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("name", ["mamba2-2.7b", "hymba-1.5b", "qwen2-7b",
                                  "whisper-large-v3"])
def test_decode_matches_prefill(built, name):
    """Teacher-forced decode must reproduce the prefill logits: feed the
    same tokens one-by-one and compare against prefill of the longer
    prompt."""
    cfg, api, params = built(name)
    batch = _batch(cfg)
    tokens = batch["tokens"]
    # prefill on the first S-1 tokens, then decode token S-1
    short = dict(batch, tokens=tokens[:, :-1])
    _, cache = api.prefill(params, short, MAXLEN)
    logits_dec, _ = api.decode_step(params, cache, tokens[:, -1])
    logits_full, _ = api.prefill(params, batch, MAXLEN)
    np.testing.assert_allclose(
        np.asarray(logits_dec, np.float32),
        np.asarray(logits_full, np.float32), rtol=2e-3, atol=2e-3)


def test_decode_inplace_matches_scan(built):
    """The fori_loop in-place-cache decode must equal the scan decode."""
    import dataclasses

    cfg, api, params = built("qwen2-7b")
    batch = _batch(cfg)
    _, cache = api.prefill(params, batch, MAXLEN)
    tok = jnp.zeros((B,), jnp.int32)
    want, cache_w = api.decode_step(params, cache, tok)
    cfg2 = dataclasses.replace(cfg, decode_inplace_cache=True)
    from repro.models.registry import get_model as _gm

    api2 = _gm(cfg2)
    got, cache_g = api2.decode_step(params, cache, tok)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(cache_g["k"], np.float32),
                               np.asarray(cache_w["k"], np.float32),
                               rtol=2e-4, atol=2e-4)
