"""The two-layer GAT on its normal path (``gat_two_layer`` → fused
attention kernels, interpreted on the CPU) against the plain float32
reference (``repro.models.gat_reference``), on seeded random weights: the
logits and the gradient of every parameter, with and without dropout
masks, on a graph that has a row holding only its self-loop.

Tolerances: logits 2e-5 and gradients 5e-5, relative to the largest
magnitude.  Both sides compute in float32; they differ in summation
order (the kernels' window products and running accumulators against
XLA's segment sums) and in the head sums, so agreement is to a few
float32 roundings of the largest terms, not bit for bit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import gat_reference as ref
from repro.models.layers import gat_two_layer

N, F_IN, C = 300, 24, 3
LOGITS_TOL, GRAD_TOL = 2e-5, 5e-5
LONELY = 17  # the row that holds only its self-loop


def _graph(seed=0, n_edges=900):
    """Symmetric random edges plus every self-loop, CSR-sorted; row
    ``LONELY`` keeps only its self-loop."""
    rng = np.random.default_rng(seed)
    u, v = rng.integers(0, N, (2, n_edges))
    keep = (u != v) & (u != LONELY) & (v != LONELY)
    u, v = u[keep], v[keep]
    rows = np.concatenate([u, v, np.arange(N)])
    cols = np.concatenate([v, u, np.arange(N)])
    pairs = np.unique(np.stack([rows, cols], 1), axis=0)  # sorted by row
    return (jnp.asarray(pairs[:, 0], jnp.int32),
            jnp.asarray(pairs[:, 1], jnp.int32))


def _params(key, heads, hidden):
    ks = jax.random.split(key, 8)
    g = lambda k, shape: jax.random.normal(k, shape) * (2.0 / sum(shape)) ** 0.5  # noqa: E731
    return {"w0": g(ks[0], (F_IN, heads * hidden)),
            "al0": g(ks[1], (heads, hidden)), "ar0": g(ks[2], (heads, hidden)),
            "b0": 0.1 * jax.random.normal(ks[3], (heads * hidden,)),
            "w1": g(ks[4], (heads * hidden, heads * C)),
            "al1": g(ks[5], (heads, C)), "ar1": g(ks[6], (heads, C)),
            "b1": 0.1 * jax.random.normal(ks[7], (C,))}


def _keeps(key, nnz, heads, hidden, rate=0.6):
    shapes = {"x0": (N, F_IN), "coef0": (nnz, heads),
              "x1": (N, heads * hidden), "coef1": (nnz, heads)}
    keys = dict(zip(shapes, jax.random.split(key, len(shapes))))
    return {k: jnp.where(jax.random.bernoulli(keys[k], 1 - rate, s),
                         1.0 / (1 - rate), 0.0) for k, s in shapes.items()}


def _close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    assert np.max(np.abs(got - want)) <= tol * scale, (
        np.max(np.abs(got - want)) / scale)


@pytest.mark.parametrize("masked", [False, True], ids=["eval", "dropout"])
@pytest.mark.parametrize("heads", [2, 3])
def test_gat_matches_reference(heads, masked):
    rows, cols = _graph(heads)
    hidden = 4
    key = jax.random.PRNGKey(heads)
    kx, kp, kk, ky = jax.random.split(key, 4)
    x = jax.random.normal(kx, (N, F_IN))
    y = jax.random.randint(ky, (N,), 0, C)
    train = jnp.arange(0, N, 5)
    params = _params(kp, heads, hidden)
    keeps = _keeps(kk, rows.shape[0], heads, hidden) if masked else None

    def program_loss(p):
        logits = gat_two_layer((rows, cols, N), x, p, keeps=keeps)
        logp = jax.nn.log_softmax(logits[train], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[train][:, None], 1))

    def reference_loss(p):
        return ref.loss(p, x, y, train, rows, cols, N, keeps=keeps)

    got = gat_two_layer((rows, cols, N), x, params, keeps=keeps)
    want = ref.forward(params, x, rows, cols, N, keeps=keeps)
    assert got.shape == (N, C)
    _close(got, want, LOGITS_TOL)
    g_prog = jax.grad(program_loss)(params)
    g_ref = jax.grad(reference_loss)(params)
    for name in params:
        _close(g_prog[name], g_ref[name], GRAD_TOL)


def test_lonely_row_attends_to_itself():
    """A row whose only entry is its self-loop puts all its weight on it:
    its first-layer output is its own projection, plus the bias."""
    rows, cols = _graph(0)
    params = _params(jax.random.PRNGKey(5), 2, 4)
    x = jax.random.normal(jax.random.PRNGKey(6), (N, F_IN))
    got = ref.attention_layer(rows, cols, N, x, params["w0"], params["al0"],
                              params["ar0"], params["b0"], concat=True,
                              slope=0.2)
    want = x[LONELY] @ params["w0"] + params["b0"]
    np.testing.assert_allclose(np.asarray(got[LONELY]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    out = gat_two_layer((rows, cols, N), x, params)
    np.testing.assert_allclose(
        np.asarray(out[LONELY]),
        np.asarray(ref.forward(params, x, rows, cols, N)[LONELY]),
        rtol=1e-5, atol=1e-5)
