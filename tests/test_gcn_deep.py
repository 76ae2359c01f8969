"""The L-layer GCN with a batch norm between layers (OGB's GCN) on its
normal path — ``models.layers.gcn`` → fusion planner → ``repro.sparse.spmm``
→ the eb launches, interpreted on the CPU — against the plain float32
reference (``repro.models.gcn_reference``), on seeded random weights; the
windowed eb launch (``spmm_eb_stream``) against ``kernels/ref.py``; the
planner's launches; and the tuner's feasibility filter at ogbn-arxiv's
size.

The windowed layout runs where a launch's blocks exceed the VMEM budget;
the tests force it at a small size by lowering that budget
(``kernels.ops._VMEM_BUDGET``) for the test alone.

Tolerances, relative to the largest magnitude: logits and loss 2e-5,
gradients and running statistics 1e-4.  Both sides compute in float32 and
differ in summation order (window products and lane order against XLA's
segment sums); the batch norm divides by each column's spread, which
magnifies those roundings a little in the gradients.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import fuse as F
from repro.core.schedule import Epilogue, Schedule
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.models import gcn_reference as ref
from repro.models.layers import gcn, gcn_two_layer
from repro.sparse import CSR, matrix_stats, random_csr, spmm

N, F_IN, HIDDEN, C = 300, 24, 32, 5
EB = Schedule("eb", nnz_tile=64, col_tile=16, group_size=8)
#: a budget under every launch's resident blocks at these sizes, so each
#: runs windowed, in several row and column windows
SMALL_BUDGET = 250_000
OUT_TOL, GRAD_TOL = 2e-5, 1e-4


def _graph(seed=0, n_edges=900):
    """``D^-1/2 (A + I) D^-1/2`` of symmetric random edges, as a CSR and
    its COO entries; row 17 holds only its self-loop."""
    rng = np.random.default_rng(seed)
    u, v = rng.integers(0, N, (2, n_edges))
    keep = (u != v) & (u != 17) & (v != 17)
    u, v = u[keep], v[keep]
    pairs = np.unique(np.stack([np.concatenate([u, v, np.arange(N)]),
                                np.concatenate([v, u, np.arange(N)])], 1),
                      axis=0)
    rows, cols = pairs[:, 0], pairs[:, 1]
    deg = np.bincount(rows, minlength=N).astype(np.float64)
    vals = ((deg[rows] * deg[cols]) ** -0.5).astype(np.float32)
    indptr = np.concatenate([[0], np.cumsum(np.bincount(rows, minlength=N))])
    adj = CSR(indptr=jnp.asarray(indptr, jnp.int32),
              indices=jnp.asarray(cols, jnp.int32), vals=jnp.asarray(vals),
              shape=(N, N))
    return adj, tuple(jnp.asarray(a) for a in (rows, cols, vals))


def _problem(seed=0, layers=3):
    """Features, labels, labelled nodes, parameters (with random norm
    scales and shifts), running statistics and dropout masks."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    widths = [F_IN] + [HIDDEN] * (layers - 1) + [C]
    params, stats, keeps = {}, {}, {}
    for i in range(layers):
        shape = (widths[i], widths[i + 1])
        params[f"w{i}"] = jax.random.normal(next(ks), shape) * (2.0 / sum(shape)) ** 0.5
        params[f"b{i}"] = 0.1 * jax.random.normal(next(ks), (widths[i + 1],))
        if i < layers - 1:
            params[f"gamma{i}"] = 1.0 + 0.1 * jax.random.normal(next(ks), (HIDDEN,))
            params[f"beta{i}"] = 0.1 * jax.random.normal(next(ks), (HIDDEN,))
            stats[f"mean{i}"] = 0.1 * jax.random.normal(next(ks), (HIDDEN,))
            stats[f"var{i}"] = jnp.exp(0.1 * jax.random.normal(next(ks), (HIDDEN,)))
            keeps[f"keep{i}"] = jnp.where(
                jax.random.bernoulli(next(ks), 0.5, (N, HIDDEN)), 2.0, 0.0)
    x = jax.random.normal(next(ks), (N, F_IN))
    y = jax.random.randint(next(ks), (N,), 0, C)
    train = jnp.asarray(np.sort(np.random.default_rng(seed).choice(
        N, N // 2, replace=False)), jnp.int32)
    return x, y, train, params, stats, keeps


def _close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    assert np.max(np.abs(got - want)) <= tol * scale, (
        np.max(np.abs(got - want)), scale)


def _count(monkeypatch, name):
    calls = []
    orig = getattr(kops, name)

    def wrapper(*a, **k):
        calls.append(name)
        return orig(*a, **k)

    monkeypatch.setattr(kops, name, wrapper)
    return calls


@pytest.mark.parametrize("layout", ["resident", "windowed"])
def test_gcn_with_batch_norm_matches_reference(layout, monkeypatch):
    """Three layers with BatchNorm, ReLU and dropout: logits, loss, the
    gradient of every parameter and the running statistics after the
    step agree with the reference, under either eb layout; each layer
    is one launch of that layout."""
    adj, (rows, cols, vals) = _graph()
    x, y, train, params, stats, keeps = _problem()
    if layout == "windowed":
        monkeypatch.setattr(kops, "_VMEM_BUDGET", SMALL_BUDGET)
    launch = "_spmm_eb_stream" if layout == "windowed" else "_spmm_eb"
    calls = _count(monkeypatch, launch)

    def loss(p):
        logits, new = gcn(adj, x, p, norm=F.BatchNorm(), stats=stats,
                          keeps=keeps, schedule=EB)
        logp = jax.nn.log_softmax(logits[train], axis=-1)
        nll = -jnp.mean(jnp.take_along_axis(logp, y[train][:, None], axis=1))
        return nll, (logits, new)

    (got, (logits, new)), grads = jax.value_and_grad(loss, has_aux=True)(params)
    assert len(calls) == 3

    def ref_loss(p):
        return ref.loss(p, stats, x, y, train, rows, cols, vals, N, keeps=keeps)

    (want, want_new), want_grads = jax.value_and_grad(ref_loss, has_aux=True)(params)
    want_logits, _ = ref.forward(params, stats, x, rows, cols, vals, N, keeps=keeps)
    _close(logits, want_logits, OUT_TOL)
    _close(got, want, OUT_TOL)
    assert set(grads) == set(want_grads)
    for k in want_grads:
        if k in ("b0", "b1"):
            # a bias a norm follows cancels: its gradient is rounding
            assert float(jnp.max(jnp.abs(grads[k]))) < 1e-6, k
            continue
        _close(grads[k], want_grads[k], GRAD_TOL)
    assert set(new) == set(want_new) == {"mean0", "var0", "mean1", "var1"}
    for k in want_new:
        _close(new[k], want_new[k], GRAD_TOL)


def test_gcn_in_evaluation_keeps_the_running_statistics():
    """In evaluation the norm takes the running statistics as given and
    leaves them unchanged; no dropout."""
    adj, (rows, cols, vals) = _graph()
    x, _, _, params, stats, _ = _problem()
    logits, out = gcn(adj, x, params, norm=F.BatchNorm(training=False),
                      stats=stats, schedule=EB)
    h = x
    for i in range(3):
        z = ref.propagate(rows, cols, vals,
                          jnp.matmul(h, params[f"w{i}"],
                                     precision=jax.lax.Precision.HIGHEST),
                          N) + params[f"b{i}"]
        if i < 2:
            z = ((z - stats[f"mean{i}"]) / jnp.sqrt(stats[f"var{i}"] + ref.EPS)
                 * params[f"gamma{i}"] + params[f"beta{i}"])
            z = jnp.maximum(z, 0.0)
        h = z
    _close(logits, h, OUT_TOL)
    for k in stats:
        assert out[k] is stats[k]


def test_deep_chain_plans_a_launch_a_layer_and_ends_folds_at_norms():
    """Three layers with norms plan three Pallas launches, each SpMM with
    its bias folded, and a norm launch (XLA, with its ReLU) between."""
    adj, _ = _graph()
    _, _, _, params, stats, keeps = _problem()
    ws = tuple(params[f"w{i}"] for i in range(3))
    bs = tuple(params[f"b{i}"] for i in range(3))
    norms = [{"gamma": params[f"gamma{i}"], "beta": params[f"beta{i}"],
              "mean": stats[f"mean{i}"], "var": stats[f"var{i}"]}
             for i in range(2)]
    chain, _ = F.gcn_chain(adj, ws, bs, norm=F.BatchNorm(), norms=norms,
                           keeps=[keeps["keep0"], keeps["keep1"]], schedule=EB)
    p = F.plan(chain)
    assert p.n_launches == 3
    kinds = [(ln.anchor.kind, ln.epilogue) for ln in p.launches]
    assert kinds == [("spmm", Epilogue(bias=True)),
                     ("norm", Epilogue(activation="relu")),
                     ("spmm", Epilogue(bias=True)),
                     ("norm", Epilogue(activation="relu")),
                     ("spmm", Epilogue(bias=True))]
    assert [ln.anchor.label for ln in p.launches if ln.anchor.kind == "norm"] == [
        "gcn.layer0", "gcn.layer1"]
    split = [r for r in p.reasons if r]
    assert sum("statistics over every row" in r for r in split) == 2


def test_two_layer_plan_is_unchanged():
    """``gcn_two_layer``'s chain and plan are Kipf and Welling's: two
    launches, the first with bias and ReLU, the second with its bias,
    and the same result as the L = 2 case of ``gcn``."""
    adj, _ = _graph()
    x, _, _, params, _, _ = _problem(layers=2)
    w0, w1, b0, b1 = (params[k] for k in ("w0", "w1", "b0", "b1"))
    chain, _ = F.gcn_chain(adj, (w0, w1), (b0, b1), schedule=EB)
    assert [n.tag for n in chain] == ["spmm", "ewise:[relu+b]", "spmm", "ewise:[b]"]
    p = F.plan(chain)
    assert p.n_launches == 2
    assert [ln.epilogue for ln in p.launches] == [
        Epilogue(activation="relu", bias=True), Epilogue(bias=True)]
    out = gcn_two_layer(adj, x, w0, w1, b0, b1, schedule=EB)
    logits, stats = gcn(adj, x, {"w0": w0, "w1": w1, "b0": b0, "b1": b1},
                        schedule=EB)
    assert stats == {}
    np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))


def _rows_empty(csr, lo, hi):
    d = np.array(csr.todense())
    d[lo:hi] = 0.0
    return CSR.fromdense(jnp.asarray(d))


#: case -> (epilogue, rows emptied): a window with no entry at all still
#: gets its output zeroed and its epilogue
WINDOWED = {
    "plain": (Epilogue(), None),
    "bias_relu": (Epilogue(activation="relu", bias=True), None),
    "residual": (Epilogue(bias=True, residual=True), None),
    "empty_row_window": (Epilogue(activation="relu", bias=True), (80, 200)),
}


@pytest.mark.parametrize("case", list(WINDOWED))
def test_windowed_spmm_eb_matches_reference(case, monkeypatch):
    """The windowed launch (several row and column windows, the last of
    each running past the array) against ``kernels/ref.py``, forward and
    gradients (values through their scatter into the blocked lanes)."""
    ep, empty = WINDOWED[case]
    csr = random_csr(300, 280, density=0.03, skew=1.0, seed=3)
    if empty:
        csr = _rows_empty(csr, *empty)
    b = jax.random.normal(jax.random.PRNGKey(0), (280, 37))
    bias = jax.random.normal(jax.random.PRNGKey(1), (37,))
    res = jax.random.normal(jax.random.PRNGKey(2), (300, 37))
    kw = dict(bias=bias if ep.bias else None,
              residual=res if ep.residual else None, epilogue=ep)
    monkeypatch.setattr(kops, "_VMEM_BUDGET", SMALL_BUDGET)
    windows = kops.eb_stream_windows(csr.shape, 37, EB)
    assert windows is not None and windows[0] < 300 and windows[1] < 280
    if empty:
        assert empty[0] <= windows[0] and 2 * windows[0] <= empty[1]
    calls = _count(monkeypatch, "_spmm_eb_stream")
    got = spmm(csr, b, EB, **kw)
    assert len(calls) == 1
    coo = csr.tocoo()
    want = ep.apply(kref.spmm_coo_ref(coo.rows, coo.cols, coo.vals, b, 300),
                    bias=kw["bias"], residual=kw["residual"])
    _close(got, want, OUT_TOL)

    def loss(vals, bb, impl):
        a = CSR(indptr=csr.indptr, indices=csr.indices, vals=vals,
                shape=csr.shape)
        return jnp.sum(jnp.sin(spmm(a, bb, EB, impl=impl, **kw)))

    g = jax.grad(loss, argnums=(0, 1))(csr.vals, b, "pallas")
    gr = jax.grad(loss, argnums=(0, 1))(csr.vals, b, "ref")
    for a, r in zip(g, gr):
        _close(a, r, GRAD_TOL)


def test_blocked_layout_holds_every_entry_once():
    """The blocked lanes are the matrix's entries, each once, padding
    with value 0, a row window's tiles consecutive and none without a
    tile; its global lanes rebuild the matrix; a GroupedCOO of the same
    matrix blocks to the same lanes."""
    csr = _rows_empty(random_csr(300, 280, density=0.03, seed=5), 80, 200)
    blk = csr.blocked(64, (64, 96))
    assert blk.nnz == csr.nnz and blk.nnz_padded % 64 == 0
    tr = np.asarray(blk.tile_rows)
    assert np.all(np.diff(tr) >= 0) and set(tr.tolist()) == set(range(5))
    rows, cols = blk.global_lanes()
    dense = np.zeros((300, 280), np.float32)
    np.add.at(dense, (np.asarray(rows), np.minimum(np.asarray(cols), 279)),
              np.asarray(blk.vals))
    np.testing.assert_array_equal(dense, np.asarray(csr.todense()))
    pos = np.asarray(blk.positions())
    assert len(set(pos.tolist())) == csr.nnz
    np.testing.assert_array_equal(np.asarray(blk.vals)[pos], np.asarray(csr.vals))
    again = csr.grouped(64).blocked((64, 96))
    for k in ("rows", "cols", "vals", "tile_rows", "tile_cols"):
        np.testing.assert_array_equal(np.asarray(getattr(again, k)),
                                      np.asarray(getattr(blk, k)))


#: ogbn-arxiv's adjacency: 169,343 rows, 2,484,941 entries with self-loops
ARXIV = {"n_rows": 169343, "n_cols": 169343, "nnz": 2484941,
         "row_mean": 14.67, "row_cv": 1.38, "row_max": 2701}


def test_tuner_pool_at_arxiv_size_holds_only_layouts_that_fit():
    """At ogbn-arxiv's size every eb candidate stays, counted windowed
    within the budget; an rb candidate, whose B is whole-K, is dropped."""
    from repro.core.selector import candidate_schedules
    from repro.tune.search import _feasible

    cands = candidate_schedules(256)
    kept = _feasible(cands, ARXIV)
    assert [s for s in kept] == [s for s in cands if s.kernel == "eb"]
    for s in kept:
        windows = kops.eb_stream_windows((ARXIV["n_rows"], ARXIV["n_cols"]),
                                         256, s)
        assert windows is not None
        need = kops.vmem_footprint_eb(ARXIV["n_cols"], ARXIV["n_rows"], s,
                                      windows)
        assert need <= kops._VMEM_BUDGET


def test_tuner_pool_with_nothing_that_fits_raises():
    """A pool where nothing fits raises rather than handing the compiler
    a schedule it must refuse."""
    from repro.tune.search import _feasible

    rb = [Schedule("rb", row_tile=8, col_tile=128)]
    with pytest.raises(ValueError, match="no candidate schedule fits"):
        _feasible(rb, ARXIV)


def test_arxiv_size_runs_windowed_and_pubmed_size_resident():
    """The layout follows the operand and output bytes: at ogbn-arxiv's
    rows each width of OGB's GCN runs windowed, at PubMed's the launch
    stays resident."""
    sched = Schedule.auto(ARXIV, 256)
    for n in (256, 40):
        assert kops.eb_stream_windows((169343, 169343), n, sched) is not None
    pubmed = matrix_stats(random_csr(400, 400, density=0.01, seed=1))
    pubmed.update(n_rows=19717, n_cols=19717)
    assert kops.eb_stream_windows((19717, 19717), 16, Schedule.auto(pubmed, 16)) is None
