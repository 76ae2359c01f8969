"""Fused sparse attention full-pipeline tests (ISSUE 5): the fused
*backward* Pallas kernel (dQ/dK/dV parity vs the spec-recompute VJP),
one-launch multi-head batching, value rows wider than a vreg, CSR
stored values as an additive score bias, f32-forced score accumulation
for low-precision inputs, the fused-attention tuner's direction/head-count
cache keys, and GAT's additive score and keep mask.

Property tests run under hypothesis when installed; without it they
degrade to a fixed seed sweep covering the same edge cases (empty rows,
single-nnz patterns, ragged sizes) instead of skipping.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised in the lean container
    HAVE_HYPOTHESIS = False

from repro.core import Schedule  # noqa: E402
from repro.kernels.fused_attention import (  # noqa: E402
    SHIFT_ROOM,
    additive_shift,
    fused_sparse_attention,
    fused_sparse_attention_bwd,
    sparse_attention_bwd_ref,
    sparse_attention_ref,
)
from repro.sparse import random_csr, sparse_attention  # noqa: E402
from repro.sparse.formats import round_up  # noqa: E402

RTOL = ATOL = 1e-5
GRAD_TOL = 1e-4

SCHEDS = [
    Schedule("eb", nnz_tile=64, group_size=8, strategy="segment"),
    Schedule("eb", nnz_tile=64, group_size=32, strategy="accumulate"),
]


def _pattern(n_rows, n_cols, nnz, seed):
    rng = np.random.default_rng(seed)
    rows = np.sort(rng.integers(0, n_rows, nnz)).astype(np.int32)
    cols = rng.integers(0, n_cols, nnz).astype(np.int32)
    return jnp.asarray(rows), jnp.asarray(cols)


def _property(strategy_fn, examples, max_examples=10):
    if HAVE_HYPOTHESIS:
        def deco(f):
            return settings(max_examples=max_examples, deadline=None)(
                given(prob=strategy_fn())(f))

        return deco
    return pytest.mark.parametrize("prob", examples)


if HAVE_HYPOTHESIS:
    @st.composite
    def attn_grad_problem(draw):
        n_rows = draw(st.integers(4, 32))
        n_cols = draw(st.integers(4, 32))
        # sparse enough that empty rows and single-nnz rows are routine
        nnz = draw(st.integers(1, 3 * n_rows))
        d = draw(st.sampled_from([4, 8]))
        dv = draw(st.sampled_from([4, 8]))
        seed = draw(st.integers(0, 2 ** 16))
        return n_rows, n_cols, nnz, d, dv, seed
else:
    attn_grad_problem = None

GRAD_EXAMPLES = [
    (4, 4, 1, 4, 4, 0),             # single nnz in the whole pattern
    (32, 20, 22, 8, 8, 1),          # most rows empty
    (20, 32, 60, 8, 4, 2),          # dense-ish rows
    (13, 9, 40, 4, 8, 3),           # ragged sizes
]


# ---------------------------------------------------------------------------
# Backward kernel: dQ/dK/dV parity vs the spec-recompute VJP
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sched", SCHEDS, ids=lambda s: s.strategy)
@_property(attn_grad_problem, GRAD_EXAMPLES, max_examples=10)
def test_fused_backward_grad_parity(sched, prob):
    n_rows, n_cols, nnz, d, dv, seed = prob
    rows, cols = _pattern(n_rows, n_cols, nnz, seed)
    key = jax.random.PRNGKey(seed)
    kq, kk, kv, kt = jax.random.split(key, 4)
    q = jax.random.normal(kq, (n_rows, d))
    k = jax.random.normal(kk, (n_cols, d))
    v = jax.random.normal(kv, (n_cols, dv))
    tgt = jax.random.normal(kt, (n_rows, dv))

    def loss_fused(qkv):
        out = sparse_attention((rows, cols, n_rows), *qkv, schedule=sched)
        return jnp.sum((out - tgt) ** 2)

    def loss_spec(qkv):
        out = sparse_attention_ref(rows, cols, *qkv, n_rows=n_rows)
        return jnp.sum((out - tgt) ** 2)

    g_f = jax.grad(loss_fused)((q, k, v))
    g_s = jax.grad(loss_spec)((q, k, v))
    for gf, gs in zip(g_f, g_s):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gs),
                                   rtol=GRAD_TOL, atol=GRAD_TOL)


def test_fused_backward_kernel_matches_spec_vjp_directly():
    """Kernel-level parity (no autodiff plumbing): the fused backward's
    dQ/dK/dV against ``sparse_attention_bwd_ref`` over a multi-nnz-tile
    pattern, with and without a score bias."""
    rng = np.random.default_rng(11)
    R, C, nnz, d, dv = 19, 15, 70, 8, 6
    rows, cols = _pattern(R, C, nnz, 11)
    nnz_tile = 32
    nnz_pad = round_up(nnz, nnz_tile)
    rows_p = jnp.pad(rows, (0, nnz_pad - nnz))
    cols_p = jnp.pad(cols, (0, nnz_pad - nnz))
    q = jax.random.normal(jax.random.PRNGKey(0), (R, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (C, 1, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (C, 1, dv))
    dout = jax.random.normal(jax.random.PRNGKey(3), (R, 1, dv))
    bias = jnp.asarray(rng.standard_normal(nnz).astype(np.float32))
    scale = d ** -0.5
    for b in (None, bias):
        b_p = None if b is None else jnp.pad(b, (0, nnz_pad - nnz))
        out, m, l = fused_sparse_attention(
            rows_p, cols_p, q, k, v, n_rows=R, nnz=nnz, nnz_tile=nnz_tile,
            scale=scale, group_size=8, bias=b_p)
        dq, dk, dv_ = fused_sparse_attention_bwd(
            rows_p, cols_p, q, k, v, out, dout, m, l, n_rows=R, nnz=nnz,
            nnz_tile=nnz_tile, scale=scale, group_size=8, bias=b_p)
        wq, wk, wv = sparse_attention_bwd_ref(
            rows, cols, q[:, 0], k[:, 0], v[:, 0], dout[:, 0], n_rows=R,
            scale=scale, bias=b)
        np.testing.assert_allclose(np.asarray(dq[:, 0]), np.asarray(wq),
                                   rtol=GRAD_TOL, atol=GRAD_TOL)
        np.testing.assert_allclose(np.asarray(dk[:, 0]), np.asarray(wk),
                                   rtol=GRAD_TOL, atol=GRAD_TOL)
        np.testing.assert_allclose(np.asarray(dv_[:, 0]), np.asarray(wv),
                                   rtol=GRAD_TOL, atol=GRAD_TOL)


def test_fused_backward_empty_and_single_nnz_rows():
    """Empty rows get exact-zero dQ rows; untouched columns get
    exact-zero dK/dV rows; a single-nnz row's softmax is constant 1 so
    its dQ/dK contribution vanishes and dV passes dout straight
    through."""
    rows = jnp.asarray([1, 3, 3], jnp.int32)
    cols = jnp.asarray([0, 1, 2], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(0), (5, 4))
    k = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    v = jax.random.normal(jax.random.PRNGKey(2), (6, 4))
    tgt = jax.random.normal(jax.random.PRNGKey(3), (5, 4))

    def loss(qkv):
        out = sparse_attention((rows, cols, 5), *qkv)
        return jnp.sum((out - tgt) ** 2)

    dq, dk, dv_ = jax.grad(loss)((q, k, v))
    g_s = jax.grad(lambda qkv: jnp.sum(
        (sparse_attention_ref(rows, cols, *qkv, n_rows=5) - tgt) ** 2))(
        (q, k, v))
    for gf, gs in zip((dq, dk, dv_), g_s):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gs),
                                   rtol=GRAD_TOL, atol=GRAD_TOL)
    assert np.all(np.asarray(dq)[[0, 2, 4]] == 0)  # empty rows
    assert np.all(np.asarray(dk)[[3, 4, 5]] == 0)  # untouched cols
    assert np.all(np.asarray(dv_)[[3, 4, 5]] == 0)
    # row 1 has a single nnz: w == 1 identically -> softmax backward
    # kills dQ for that row, and dV[0] receives dout[1] verbatim
    np.testing.assert_allclose(np.asarray(dq)[1], 0.0, atol=GRAD_TOL)


# ---------------------------------------------------------------------------
# Wide value rows
# ---------------------------------------------------------------------------


def test_forward_multi_dv_tile_probability_carry():
    """Value rows wider than a vreg (2 heads x 24 lanes, several tiles of
    lanes) must match the oracle: each lane's probabilities are spread
    over every lane of its head's values."""
    rows, cols = _pattern(14, 10, 33, 7)
    nnz_pad = round_up(33, 32)
    rows_p = jnp.pad(rows, (0, nnz_pad - 33))
    cols_p = jnp.pad(cols, (0, nnz_pad - 33))
    q = jax.random.normal(jax.random.PRNGKey(0), (14, 2, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (10, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (10, 2, 72))
    out, _, _ = fused_sparse_attention(
        rows_p, cols_p, q, k, v, n_rows=14, nnz=33, nnz_tile=32,
        scale=0.5, group_size=8)
    for h in range(2):
        want = sparse_attention_ref(rows, cols, q[:, h], k[:, h], v[:, h],
                                    n_rows=14, scale=0.5)
        np.testing.assert_allclose(np.asarray(out[:, h]), np.asarray(want),
                                   rtol=RTOL, atol=ATOL)


def test_public_api_multi_dv_tile_forward_and_grads():
    """dv > 128 drives the public path onto a multi-dv-tile grid
    (dv_tile caps at 128); forward and grads must still match the
    spec."""
    rows, cols = _pattern(10, 8, 25, 5)
    q = jax.random.normal(jax.random.PRNGKey(0), (10, 4))
    k = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
    v = jax.random.normal(jax.random.PRNGKey(2), (8, 160))
    got = np.asarray(sparse_attention((rows, cols, 10), q, k, v))
    want = np.asarray(sparse_attention_ref(rows, cols, q, k, v, n_rows=10))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    g_f = jax.grad(lambda qq: jnp.sum(
        sparse_attention((rows, cols, 10), qq, k, v) ** 2))(q)
    g_s = jax.grad(lambda qq: jnp.sum(
        sparse_attention_ref(rows, cols, qq, k, v, n_rows=10) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_s),
                               rtol=GRAD_TOL, atol=GRAD_TOL)


# ---------------------------------------------------------------------------
# Multi-head: one launch, forward + grads
# ---------------------------------------------------------------------------


def test_graph_attention_is_one_kernel_launch(monkeypatch):
    from repro.models.attention import graph_attention
    from repro.sparse import ops as sops

    adj = random_csr(12, 12, density=0.25, seed=2)
    q = jax.random.normal(jax.random.PRNGKey(0), (12, 4, 4))
    k = jax.random.normal(jax.random.PRNGKey(1), (12, 4, 4))
    v = jax.random.normal(jax.random.PRNGKey(2), (12, 4, 4))
    calls = []
    orig = sops._fused_attn_fwd

    def counting(*args, **kw):
        calls.append(1)
        return orig(*args, **kw)

    monkeypatch.setattr(sops, "_fused_attn_fwd", counting)
    out = graph_attention(adj, q, k, v)
    assert out.shape == (12, 4, 4)
    assert len(calls) == 1  # H=4 heads, ONE fused kernel launch


@pytest.mark.parametrize("sched", SCHEDS, ids=lambda s: s.strategy)
def test_multihead_grads_match_per_head_spec(sched):
    rows, cols = _pattern(16, 12, 40, 4)
    H = 3
    q = jax.random.normal(jax.random.PRNGKey(0), (16, H, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (12, H, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (12, H, 6))
    tgt = jax.random.normal(jax.random.PRNGKey(3), (16, H, 6))

    def loss_fused(qkv):
        out = sparse_attention((rows, cols, 16), *qkv, schedule=sched)
        return jnp.sum((out - tgt) ** 2)

    def loss_spec(qkv):
        qq, kk, vv = qkv
        outs = [sparse_attention_ref(rows, cols, qq[:, h], kk[:, h],
                                     vv[:, h], n_rows=16)
                for h in range(H)]
        return jnp.sum((jnp.stack(outs, axis=1) - tgt) ** 2)

    g_f = jax.grad(loss_fused)((q, k, v))
    g_s = jax.grad(loss_spec)((q, k, v))
    for gf, gs in zip(g_f, g_s):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gs),
                                   rtol=GRAD_TOL, atol=GRAD_TOL)


def test_multihead_rejects_mismatched_head_counts():
    rows, cols = _pattern(8, 8, 10, 0)
    q = jax.random.normal(jax.random.PRNGKey(0), (8, 2, 4))
    k = jax.random.normal(jax.random.PRNGKey(1), (8, 3, 4))
    v = jax.random.normal(jax.random.PRNGKey(2), (8, 2, 4))
    with pytest.raises(ValueError, match="head"):
        sparse_attention((rows, cols, 8), q, k, v)
    # mixed 2-D / 3-D operands get the same clear error, not a shape
    # unpack failure deep inside the kernel wrapper
    with pytest.raises(ValueError, match="head"):
        sparse_attention((rows, cols, 8), q[:, 0], k, v)


# ---------------------------------------------------------------------------
# CSR stored values = additive score bias (ISSUE 5 satellite)
# ---------------------------------------------------------------------------


def test_csr_values_bias_scores_and_all_ones_is_pure_pattern():
    from repro.sparse.formats import CSR

    adj = random_csr(14, 14, density=0.2, seed=3)
    coo = adj.tocoo()
    q = jax.random.normal(jax.random.PRNGKey(0), (14, 4))
    k = jax.random.normal(jax.random.PRNGKey(1), (14, 4))
    v = jax.random.normal(jax.random.PRNGKey(2), (14, 4))
    got = np.asarray(sparse_attention(adj, q, k, v))
    biased = np.asarray(sparse_attention_ref(coo.rows, coo.cols, q, k, v,
                                             n_rows=14, bias=coo.vals))
    plain = np.asarray(sparse_attention_ref(coo.rows, coo.cols, q, k, v,
                                            n_rows=14))
    np.testing.assert_allclose(got, biased, rtol=RTOL, atol=ATOL)
    # random values genuinely move the result (they used to be ignored)
    assert not np.allclose(got, plain, rtol=1e-3, atol=1e-3)
    # an all-ones "pattern" CSR shifts every score in a row equally,
    # which the softmax cancels -> identical to the pure pattern
    ones = CSR(indptr=adj.indptr, indices=adj.indices,
               vals=jnp.ones_like(adj.vals), shape=adj.shape)
    got_ones = np.asarray(sparse_attention(ones, q, k, v))
    np.testing.assert_allclose(got_ones, plain, rtol=RTOL, atol=ATOL)
    # ref impl honors the bias identically
    np.testing.assert_allclose(
        np.asarray(sparse_attention(adj, q, k, v, impl="ref")), biased,
        rtol=RTOL, atol=ATOL)


def test_csr_values_bias_flows_through_grads():
    adj = random_csr(12, 12, density=0.25, seed=6)
    coo = adj.tocoo()
    q = jax.random.normal(jax.random.PRNGKey(0), (12, 4))
    k = jax.random.normal(jax.random.PRNGKey(1), (12, 4))
    v = jax.random.normal(jax.random.PRNGKey(2), (12, 4))
    g_f = jax.grad(lambda qq: jnp.sum(sparse_attention(adj, qq, k, v) ** 2))(q)
    g_s = jax.grad(lambda qq: jnp.sum(sparse_attention_ref(
        coo.rows, coo.cols, qq, k, v, n_rows=12, bias=coo.vals) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_s),
                               rtol=GRAD_TOL, atol=GRAD_TOL)


# ---------------------------------------------------------------------------
# Low-precision inputs: f32-forced score accumulation (ISSUE 5 satellite)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16])
def test_low_precision_inputs_match_f32_upcasting_oracle(dtype):
    """The NEG_INF = -1e30 masked-lane floor overflows fp16 to -inf (and
    bf16 loses the exp cancellation) unless scores accumulate in f32;
    the kernel must match the (already f32-upcasting) spec oracle to a
    low-precision rounding, forward and backward, with no NaN/inf."""
    rows, cols = _pattern(20, 16, 50, 8)
    q = jax.random.normal(jax.random.PRNGKey(0), (20, 8)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (16, 8)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (16, 4)).astype(dtype)
    got = np.asarray(sparse_attention((rows, cols, 20), q, k, v),
                     np.float32)
    want = np.asarray(sparse_attention_ref(rows, cols, q, k, v, n_rows=20))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    # backward: finite and parity with the spec VJP on the same inputs
    g_f = jax.grad(lambda qq: jnp.sum(sparse_attention(
        (rows, cols, 20), qq, k, v).astype(jnp.float32) ** 2))(q)
    g_s = jax.grad(lambda qq: jnp.sum(sparse_attention_ref(
        rows, cols, qq, k, v, n_rows=20) ** 2))(q)
    assert np.all(np.isfinite(np.asarray(g_f, np.float32)))
    np.testing.assert_allclose(np.asarray(g_f, np.float32),
                               np.asarray(g_s, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# Tuner: fwd/bwd + head count are distinct cache keys
# ---------------------------------------------------------------------------


def test_attention_tuner_keys_and_replay():
    from repro.tune import (
        ScheduleCache,
        attention_cache_key,
        tune_sparse_attention,
    )

    rows, cols = _pattern(24, 20, 60, 9)
    kf = attention_cache_key(rows, 24, n_cols=20, d=8, dv=6, n_heads=1,
                             direction="fwd")
    kb = attention_cache_key(rows, 24, n_cols=20, d=8, dv=6, n_heads=1,
                             direction="bwd")
    k4 = attention_cache_key(rows, 24, n_cols=20, d=8, dv=6, n_heads=4,
                             direction="fwd")
    kbias = attention_cache_key(rows, 24, n_cols=20, d=8, dv=6,
                                n_heads=1, direction="fwd", has_bias=True)
    kkv = attention_cache_key(rows, 24, n_cols=4096, d=8, dv=6,
                              n_heads=1, direction="fwd")
    assert len({kf, kb, k4, kbias, kkv}) == 5  # all distinct
    assert kf.endswith("fwd") and "|H4|" in k4 and "bwd" in kb
    with pytest.raises(ValueError, match="direction"):
        attention_cache_key(rows, 24, n_cols=20, d=8, dv=6, n_heads=1,
                            direction="sideways")

    q = jax.random.normal(jax.random.PRNGKey(0), (24, 8))
    k = jax.random.normal(jax.random.PRNGKey(1), (20, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (20, 6))
    cache = ScheduleCache(path=None)
    measured = []

    def fake_measure(s):
        measured.append(s)
        # prefer one specific point so the winner is deterministic
        return 1e-6 if (s.nnz_tile, s.group_size) == (128, 32) else 2e-6

    res_f = tune_sparse_attention(rows, cols, q, k, v, n_rows=24,
                                  cache=cache, measure=fake_measure)
    res_b = tune_sparse_attention(rows, cols, q, k, v, n_rows=24,
                                  direction="bwd", cache=cache,
                                  measure=fake_measure)
    assert res_f.key == kf and res_b.key == kb
    assert res_f.schedule.nnz_tile == 128
    assert not res_f.from_cache and not res_b.from_cache
    # replay: zero measurements on a second identical query
    n = len(measured)
    hit = tune_sparse_attention(rows, cols, q, k, v, n_rows=24,
                                cache=cache, measure=fake_measure)
    assert hit.from_cache and len(measured) == n


def test_attention_tuner_bwd_measures_rectangular_pattern():
    """direction='bwd' with the real kernel objective on a rectangular
    pattern (n_rows != n_cols): the cotangent must take the OUTPUT's
    shape, not v's (regression — they only coincide on square
    patterns)."""
    from repro.tune import ScheduleCache, tune_sparse_attention

    rows, cols = _pattern(10, 7, 15, 4)
    q = jax.random.normal(jax.random.PRNGKey(0), (10, 4))
    k = jax.random.normal(jax.random.PRNGKey(1), (7, 4))
    v = jax.random.normal(jax.random.PRNGKey(2), (7, 4))
    res = tune_sparse_attention(rows, cols, q, k, v, n_rows=10,
                                direction="bwd",
                                cache=ScheduleCache(path=None),
                                warmup=0, iters=1)
    assert res.key.endswith("bwd") and res.us_per_call > 0


def test_sparse_attention_schedule_tune_end_to_end():
    """schedule="tune" measures the real fused kernel and the tuned
    schedule reproduces the oracle."""
    from repro.tune import ScheduleCache, set_default_cache

    rows, cols = _pattern(16, 12, 30, 2)
    q = jax.random.normal(jax.random.PRNGKey(0), (16, 4))
    k = jax.random.normal(jax.random.PRNGKey(1), (12, 4))
    v = jax.random.normal(jax.random.PRNGKey(2), (12, 4))
    set_default_cache(ScheduleCache(path=None))
    try:
        got = np.asarray(sparse_attention((rows, cols, 16), q, k, v,
                                          schedule="tune"))
    finally:
        set_default_cache(None)
    want = np.asarray(sparse_attention_ref(rows, cols, q, k, v, n_rows=16))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


# ---------------------------------------------------------------------------
# GAT's additive score and the keep mask on the coefficients
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "keep"])
@pytest.mark.parametrize("score", ["additive", "dot"])
@pytest.mark.parametrize("sched", SCHEDS, ids=lambda s: s.strategy)
def test_fused_kernels_score_and_keep_match_oracle(sched, score, masked):
    """Kernel-level parity of the forward's output and the backward's
    dQ/dK/dV against ``sparse_attention_ref`` and
    ``sparse_attention_bwd_ref``, head by head, for the additive score
    (LeakyReLU of per-node terms of width 1) and the dot product, with and
    without a (nnz, H) keep mask; the pattern has empty rows and spans
    several nnz tiles."""
    R, C, nnz, H, dv = 21, 17, 90, 3, 5
    d = 1 if score == "additive" else 4
    rows, cols = _pattern(R, C, nnz, 13)
    tile = sched.nnz_tile
    nnz_pad = round_up(nnz, tile)
    rows_p = jnp.pad(rows, (0, nnz_pad - nnz))
    cols_p = jnp.pad(cols, (0, nnz_pad - nnz))
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (R, H, d))
    k = jax.random.normal(ks[1], (C, H, d))
    v = jax.random.normal(ks[2], (C, H, dv))
    dout = jax.random.normal(ks[3], (R, H, dv))
    keep = (jnp.where(jax.random.bernoulli(ks[4], 0.4, (nnz, H)), 2.5, 0.0)
            if masked else None)
    keep_p = None if keep is None else jnp.pad(keep, ((0, nnz_pad - nnz), (0, 0)))
    kw = dict(n_rows=R, nnz=nnz, nnz_tile=tile, scale=0.5, score=score,
              slope=0.2, group_size=sched.group_size, strategy=sched.strategy)
    out, m, l = fused_sparse_attention(rows_p, cols_p, q, k, v, keep=keep_p,
                                       **kw)
    dq, dk, dv_ = fused_sparse_attention_bwd(rows_p, cols_p, q, k, v, out,
                                             dout, m, l, keep=keep_p, **kw)
    for h in range(H):
        kh = None if keep is None else keep[:, h]
        spec = dict(n_rows=R, scale=0.5, score=score, slope=0.2, keep=kh)
        want = sparse_attention_ref(rows, cols, q[:, h], k[:, h], v[:, h],
                                    **spec)
        np.testing.assert_allclose(np.asarray(out[:, h]), np.asarray(want),
                                   rtol=RTOL, atol=ATOL)
        grads = sparse_attention_bwd_ref(rows, cols, q[:, h], k[:, h],
                                         v[:, h], dout[:, h], **spec)
        for got, w in zip((dq[:, h], dk[:, h], dv_[:, h]), grads):
            np.testing.assert_allclose(np.asarray(got), np.asarray(w),
                                       rtol=GRAD_TOL, atol=GRAD_TOL)


def test_public_api_keep_and_additive_grads_match_spec():
    """``sparse_attention`` with the additive score and a keep mask,
    differentiated end to end through the custom VJP, against the spec
    oracle's autodiff."""
    rows, cols = _pattern(18, 18, 60, 21)
    H = 2
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    s = jax.random.normal(ks[0], (18, H, 1))
    t = jax.random.normal(ks[1], (18, H, 1))
    v = jax.random.normal(ks[2], (18, H, 6))
    keep = jnp.where(jax.random.bernoulli(ks[3], 0.5, (60, H)), 2.0, 0.0)

    def loss(impl):
        def f(args):
            out = sparse_attention((rows, cols, 18), *args, impl=impl,
                                   score="additive", keep=keep)
            return jnp.sum(jnp.sin(out))
        return f

    g_f = jax.grad(loss("pallas"))((s, t, v))
    g_s = jax.grad(loss("ref"))((s, t, v))
    for gf, gs in zip(g_f, g_s):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gs),
                                   rtol=GRAD_TOL, atol=GRAD_TOL)


# ---------------------------------------------------------------------------
# The additive score's one-pass forward: the shift from a bound
# ---------------------------------------------------------------------------


def _additive_problem(H, seed, k_scale=1.0):
    """An additive-score problem over several nnz tiles with padded lanes
    and empty rows (rows 0 and 7 hold no entry)."""
    R, C, nnz, dv = 23, 17, 90, 4
    rng = np.random.default_rng(seed)
    rows = np.sort(rng.choice([r for r in range(R) if r not in (0, 7)], nnz))
    rows, cols = jnp.asarray(rows.astype(np.int32)), jnp.asarray(
        rng.integers(0, C, nnz).astype(np.int32))
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (R, H, 1))
    k = jax.random.normal(ks[1], (C, H, 1)) * k_scale
    v = jax.random.normal(ks[2], (C, H, dv))
    return rows, cols, q, k, v, ks[3]


def _leaky(x, slope=0.2):
    return jnp.where(x > 0, x, slope * x)


def _bound(q, k):
    """The upper bound alone: LeakyReLU(q + max k), at or above every
    score of its row."""
    return _leaky(q[:, :, 0] + jnp.max(k[:, :, 0], axis=0))


def _two_sided(q, k):
    """The bound branch's shift, min(hi, lo + SHIFT_ROOM)."""
    lo = _leaky(q[:, :, 0] + jnp.min(k[:, :, 0], axis=0))
    return jnp.minimum(_bound(q, k), lo + SHIFT_ROOM)


def _exact(rows, cols, q, k, R):
    m = jax.ops.segment_max(_leaky(q[rows, :, 0] + k[cols, :, 0]), rows,
                            num_segments=R)
    return jnp.where(jnp.isfinite(m), m, 0.0)


def _forward(rows, cols, q, k, v, shift):
    nnz, tile = rows.shape[0], 64
    nnz_pad = round_up(nnz, tile)
    return fused_sparse_attention(
        _padded(rows, nnz_pad), _padded(cols, nnz_pad), q, k, v, shift=shift,
        n_rows=q.shape[0], nnz=nnz, nnz_tile=tile, group_size=8,
        score="additive", slope=0.2)[0]


def _oracle(rows, cols, q, k, v, h):
    return np.asarray(sparse_attention_ref(
        rows, cols, q[:, h], k[:, h], v[:, h], n_rows=q.shape[0],
        score="additive"))


def _padded(x, nnz_pad):
    return None if x is None else jnp.pad(
        x, ((0, nnz_pad - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


@pytest.mark.parametrize("biased", [False, True], ids=["pattern", "csr_bias"])
@pytest.mark.parametrize("masked", [False, True], ids=["no_mask", "keep"])
@pytest.mark.parametrize("H", [1, 8])
def test_one_pass_additive_forward_matches_oracle_and_two_phase(H, masked,
                                                                biased):
    """The forward handed ``additive_shift``'s table runs one pass, and
    matches ``sparse_attention_ref`` and the two-phase kernel to float32
    rounding, with 1 and 8 heads, with and without a keep mask and a
    value bias, over empty rows and padded lanes; its (m, l) are the
    two-phase kernel's up to the shift: l·e^m is the same."""
    rows, cols, q, k, v, key = _additive_problem(H, 31 + H)
    R, nnz, tile = q.shape[0], rows.shape[0], 64
    nnz_pad = round_up(nnz, tile)
    bias = (jax.random.normal(key, (nnz,)) if biased else None)
    keep = (jnp.where(jax.random.bernoulli(key, 0.5, (nnz, H)), 2.0, 0.0)
            if masked else None)
    kw = dict(n_rows=R, nnz=nnz, nnz_tile=tile, group_size=8,
              score="additive", slope=0.2, bias=_padded(bias, nnz_pad),
              keep=_padded(keep, nnz_pad))
    rows_p, cols_p = _padded(rows, nnz_pad), _padded(cols, nnz_pad)
    shift = additive_shift(rows, cols, q, k, bias, n_rows=R, slope=0.2)
    out1, m1, l1 = fused_sparse_attention(rows_p, cols_p, q, k, v,
                                          shift=shift, **kw)
    out2, m2, l2 = fused_sparse_attention(rows_p, cols_p, q, k, v, **kw)
    np.testing.assert_array_equal(np.asarray(m1), np.asarray(shift))
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=RTOL, atol=ATOL)
    full = np.asarray(l2) > 0
    np.testing.assert_allclose(
        np.asarray(l1 * jnp.exp(m1 - m2))[full], np.asarray(l2)[full],
        rtol=RTOL)
    for h in range(H):
        want = sparse_attention_ref(
            rows, cols, q[:, h], k[:, h], v[:, h], n_rows=R, score="additive",
            bias=bias, keep=None if keep is None else keep[:, h])
        np.testing.assert_allclose(np.asarray(out1[:, h]), np.asarray(want),
                                   rtol=RTOL, atol=ATOL)
    assert np.all(np.asarray(out1)[[0, 7]] == 0)  # empty rows


@pytest.mark.parametrize("biased", [False, True], ids=["pattern", "csr_bias"])
def test_additive_grads_match_bwd_ref(biased):
    """``sparse_attention(score="additive")``, whose forward takes the
    bound's shift, differentiated through the custom VJP: dq, dk and dv
    against ``sparse_attention_bwd_ref``, head by head."""
    rows, cols, q, k, v, key = _additive_problem(3, 5)
    R, C, nnz = q.shape[0], k.shape[0], rows.shape[0]
    bias = jax.random.normal(key, (nnz,)) if biased else None
    adj = (rows, cols, R)
    if biased:  # the bias rides a CSR adjacency's stored values
        from repro.sparse.formats import CSR

        indptr = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(
            jnp.bincount(rows, length=R)).astype(jnp.int32)])
        adj = CSR(indptr=indptr, indices=cols, vals=bias, shape=(R, C))
    dout = jax.random.normal(jax.random.PRNGKey(9), (R, 3, v.shape[2]))
    _, vjp = jax.vjp(lambda *a: sparse_attention(
        adj, *a, schedule=SCHEDS[0], score="additive"), q, k, v)
    grads = vjp(dout)
    for h in range(3):
        want = sparse_attention_bwd_ref(
            rows, cols, q[:, h], k[:, h], v[:, h], dout[:, h], n_rows=R,
            scale=1.0, bias=bias, score="additive")
        for got, w in zip(grads, want):
            np.testing.assert_allclose(np.asarray(got[:, h]), np.asarray(w),
                                       rtol=GRAD_TOL, atol=GRAD_TOL)


def test_shift_guard_takes_exact_beyond_the_spread():
    """k of ±300 puts a row's bounds ~600 apart, past 2·60: the shift is
    the exact row max, and the forward still matches the oracle.  Forced
    onto the same input, either bound shift loses rows: the upper bound
    alone sits ~600 above a row whose columns all hold -300, so every term
    underflows and the row reads 0; the two-sided shift sits ~60 above
    the lower bound, so a row with a column at +300 overflows.  The guard
    is needed."""
    rows, cols, q, k, v, _ = _additive_problem(2, 3)
    R = q.shape[0]
    low = np.asarray(cols) < 12
    k = jnp.where(jnp.arange(k.shape[0])[:, None, None] < 12, -300.0, 300.0)
    k = k * jnp.ones((1, 2, 1))
    shift = additive_shift(rows, cols, q, k, n_rows=R, slope=0.2)
    np.testing.assert_array_equal(np.asarray(shift),
                                  np.asarray(_exact(rows, cols, q, k, R)))
    out = _forward(rows, cols, q, k, v, shift)
    under = _forward(rows, cols, q, k, v, _bound(q, k))
    over = _forward(rows, cols, q, k, v, _two_sided(q, k))
    r = np.asarray(rows)
    held = [i for i in range(R) if np.any(r == i)]
    lost = [i for i in held if np.all(low[r == i])]
    high = [i for i in held if not np.all(low[r == i])]
    assert lost and high
    for h in range(2):
        want = _oracle(rows, cols, q, k, v, h)
        np.testing.assert_allclose(np.asarray(out[:, h]), want, rtol=RTOL,
                                   atol=ATOL)
        assert np.all(np.abs(want[lost]).max(axis=-1) > 1e-3)
        assert np.all(np.asarray(under[:, h])[lost] == 0)
        assert not np.any(np.isfinite(np.asarray(over[:, h])[high]))


@pytest.mark.parametrize("case", ["nan_k", "gap_at_limit", "gap_past_limit"])
def test_shift_guard_predicate(case):
    """A k holding NaN fails ``gap <= 120`` and takes the exact row max
    (finite wherever a row's columns are).  q of 70 and k spread over
    ±60 put every row's bounds exactly 2·:data:`SHIFT_ROOM` apart: the
    bound branch's shift sits 60 above the lower bound and 60 below the
    upper, the largest term of a row whose columns reach +60 is e^60, and
    the forward matches the oracle.  A quarter more spread takes the
    exact row max."""
    rows, cols, q, k, v, _ = _additive_problem(2, 4)
    R = q.shape[0]
    if case == "nan_k":
        k = k.at[3].set(jnp.nan)
        shift = np.asarray(additive_shift(rows, cols, q, k, n_rows=R,
                                          slope=0.2))
        touched = np.zeros(R, bool)
        touched[np.asarray(rows)[np.asarray(cols) == 3]] = True
        assert touched.any() and not touched.all()
        assert np.all(np.isfinite(shift[~touched]))
        assert np.all(np.isnan(np.asarray(_bound(q, k))))
        return
    half = SHIFT_ROOM + (0.25 if case == "gap_past_limit" else 0.0)
    k = k.at[:, :, 0].set(jnp.linspace(-half, half, k.shape[0])[:, None])
    q = jnp.full_like(q, 70.0)
    assert float(jnp.max(_bound(q, k) - _leaky(q[:, :, 0] + k.min()))) == 2 * half
    shift = np.asarray(additive_shift(rows, cols, q, k, n_rows=R, slope=0.2))
    if case == "gap_past_limit":
        np.testing.assert_array_equal(
            shift, np.asarray(_exact(rows, cols, q, k, R)))
        return
    np.testing.assert_array_equal(shift, np.asarray(_two_sided(q, k)))
    np.testing.assert_array_equal(shift, 70.0)
    out = _forward(rows, cols, q, k, v, jnp.asarray(shift))
    for h in range(2):
        np.testing.assert_allclose(np.asarray(out[:, h]),
                                   _oracle(rows, cols, q, k, v, h),
                                   rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("form", ["pattern", "csr_bias"])
def test_additive_empty_pattern_gives_zero_rows(form):
    """An adjacency with no entries, as a pattern tuple or a CSR whose
    stored values are the score bias: the additive score's shift has no
    bias term to bound, the output is all zero rows, and so are the
    gradients."""
    from repro.sparse.formats import CSR

    R, C, H = 5, 4, 2
    empty = jnp.zeros(0, jnp.int32)
    adj = ((empty, empty, R) if form == "pattern" else
           CSR(indptr=jnp.zeros(R + 1, jnp.int32), indices=empty,
               vals=jnp.zeros(0, jnp.float32), shape=(R, C)))
    q, k = jnp.ones((R, H, 1)), jnp.ones((C, H, 1))
    v = jnp.ones((C, H, 3))
    out, vjp = jax.vjp(lambda *a: sparse_attention(adj, *a, score="additive"),
                       q, k, v)
    assert np.all(np.asarray(out) == 0)
    for g in vjp(jnp.ones_like(out)):
        assert np.all(np.asarray(g) == 0)

