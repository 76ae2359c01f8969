"""Mixture-of-Experts FFN with segment-group dispatch (DESIGN.md §4.1).

Dispatch is the paper's sparse–dense hybrid algebra: routing matrix
(tokens × experts, top-k sparse) times token activations. The TPU
realization uses per-expert capacity selection (zero extension = capacity
padding), grouped GEMM, and scatter-add + psum writeback — the
segment-group machinery at the collective level.

Two execution paths with identical math:
  * einsum path — the SPMD path (flop-accurate grouped GEMM per local
    expert);
  * Pallas path — ``kernels.grouped_matmul`` on the capacity-gathered
    tokens (validated in tests, CPU-interpret).

Expert parallelism: under a ``ShardingCtx`` the experts are sharded over
the model axis and tokens over the data axes via ``shard_map``; the psum
over the model axis is the 'atomic' collective writeback (DESIGN.md
changed-assumption 2).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import init_dense


@dataclasses.dataclass(frozen=True)
class ShardingCtx:
    """How model-internal shard_map regions see the mesh. ``None`` ctx (or
    axes) means single-shard execution (smoke tests)."""

    mesh: object = None
    data_axes: tuple = ()
    model_axis: str | None = None


def init_moe(cfg, key):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    s = d ** -0.5
    so = f ** -0.5
    return {
        "router": init_dense(k1, d, e, "float32")["w"],
        "wg": (jax.random.normal(k2, (e, d, f)) * s).astype(cfg.param_dtype),
        "wi": (jax.random.normal(k3, (e, d, f)) * s).astype(cfg.param_dtype),
        "wo": (jax.random.normal(k4, (e, f, d)) * so).astype(cfg.param_dtype),
    }


def _capacity(cfg, t_local: int, factor: float | None = None) -> int:
    if factor is None:
        factor = cfg.capacity_factor
    cap = int(t_local * cfg.experts_per_token * factor / cfg.n_experts)
    return min(max(8, cap), t_local)


def _expert_ffn(cfg, x, wg, wi, wo, gates, capacity, use_pallas,
                dispatch=None, combine: str = "sum"):
    """Local computation: x (T, D) tokens; wg/wi/wo (E_loc, D, F)/(E_loc, F,
    D); gates (T, E_loc) combine weights (0 when not routed). Returns the
    partial output (T, D) for these experts.  ``dispatch`` (a
    ``repro.tune.MoeDispatchSchedule``) overrides the static tile
    defaults of the Pallas path; ``None`` keeps them.  ``combine`` is
    the gate-weighted writeback monoid ('sum' / 'min' / 'mean' —
    ``repro.fuse.moe_combine``)."""
    t, d = x.shape
    e_loc = wg.shape[0]
    # per-expert capacity selection: top-C tokens by gate weight. Tokens
    # with gate 0 may be selected when a local expert is under capacity —
    # they contribute 0 (zero extension).
    topv, topi = jax.lax.top_k(gates.T, capacity)  # (E_loc, C)
    xg = jnp.take(x, topi.reshape(-1), axis=0).reshape(e_loc, capacity, d)

    if use_pallas:
        from ..core.schedule import Epilogue
        from ..kernels.grouped_matmul import fit_tile
        from ..kernels.ops import grouped_matmul

        f = wg.shape[-1]
        tt = dispatch.token_tile if dispatch is not None else 128
        dt = fit_tile(d, dispatch.d_tile if dispatch is not None else 128)
        ft = fit_tile(f, dispatch.f_tile if dispatch is not None else 128)
        tile = min(capacity, tt)
        cap_pad = ((capacity + tile - 1) // tile) * tile
        if cap_pad != capacity:
            xg = jnp.pad(xg, ((0, 0), (0, cap_pad - capacity), (0, 0)))
        tiles_per_e = cap_pad // tile
        tile_experts = jnp.repeat(jnp.arange(e_loc, dtype=jnp.int32),
                                  tiles_per_e)
        flat = xg.reshape(e_loc * cap_pad, d)

        def gmm(x_, w_, contract_tile, out_tile, epilogue=Epilogue()):
            return grouped_matmul(x_, tile_experts, w_, token_tile=tile,
                                  d_tile=contract_tile, f_tile=out_tile,
                                  epilogue=epilogue)

        # the up-projections contract D and emit F; the down-projection
        # contracts F and emits D — tiles are passed per role, never
        # inferred from shapes (d == f would make that ambiguous).  The
        # gate projection's SiLU is fused onto the GEMM's output block
        # (the repro.fuse grouped_matmul→ewise chain, pre-planned): one
        # launch per tile instead of a GEMM pass + an XLA silu pass.
        h = gmm(flat, wg, dt, ft,
                epilogue=Epilogue(activation="silu")) * gmm(flat, wi,
                                                            dt, ft)
        y = gmm(h.astype(x.dtype), wo, ft, dt)
        y = y.reshape(e_loc, cap_pad, d)[:, :capacity]
    else:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, wg)) * jnp.einsum(
            "ecd,edf->ecf", xg, wi)
        y = jnp.einsum("ecf,efd->ecd", h.astype(x.dtype), wo)

    from ..fuse.execute import moe_combine

    return moe_combine(y.reshape(-1, d), topi.reshape(-1),
                       topv.reshape(-1), t, op=combine)


def _route(cfg, x, router):
    """Router: top-k gates. Returns (gates_dense (T, E) with zeros off the
    top-k, probs (T, E) for the aux loss)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.experts_per_token)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(probs.shape[0])[:, None], topi].set(topv)
    return gates, probs


def _aux_loss(cfg, gates, probs):
    """Switch-style load-balance loss over the local token shard."""
    f = jnp.mean((gates > 0).astype(jnp.float32), axis=0)  # dispatch frac
    p = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(f * p)


def apply_moe(cfg, p, x2d, ctx: ShardingCtx | None = None, *,
              dispatch=None, combine: str = "sum"):
    """x2d: (T, D) tokens (sharded over data axes under ctx). Returns
    (out (T, D), aux_loss scalar).  ``dispatch`` (a
    ``repro.tune.MoeDispatchSchedule``, e.g. from
    :func:`moe_tune_dispatch`) replaces the static token-tile/capacity
    defaults; ``None`` keeps the config's static choice.  ``combine``
    picks the expert→token writeback monoid ('sum' default; 'min' /
    'mean' run the same gate-weighted scatter under those monoids —
    ``repro.fuse.moe_combine``).  Non-additive combines are single-shard
    only: the expert-parallel psum writeback composes additive partials
    and cannot carry a min/mean across shards."""
    use_pallas = cfg.moe_pallas_dispatch
    cap_factor = dispatch.capacity_factor if dispatch is not None else None

    if ctx is None or ctx.mesh is None or ctx.model_axis is None:
        gates, probs = _route(cfg, x2d, p["router"])
        cap = _capacity(cfg, x2d.shape[0], cap_factor)
        out = _expert_ffn(cfg, x2d, p["wg"], p["wi"], p["wo"], gates, cap,
                          use_pallas, dispatch, combine)
        return out.astype(x2d.dtype), _aux_loss(cfg, gates, probs)

    if combine != "sum":
        raise ValueError(
            f"combine={combine!r} requires single-shard execution: the "
            "expert-parallel psum writeback only composes additive "
            "partials")

    mesh = ctx.mesh
    dax, max_ = ctx.data_axes, ctx.model_axis
    t_local = x2d.shape[0] // int(
        functools.reduce(lambda a, b: a * b, (mesh.shape[a] for a in dax), 1))
    cap = _capacity(cfg, t_local, cap_factor)

    # expert-parallel writeback mode (DESIGN.md §12): the tuned dispatch
    # carries the collective the way Schedule carries it for SpMM —
    # 'nnz_ar' is the atomic-style psum (the historical default),
    # 'nnz_rs' reduce-scatters the partial so each model shard finalizes
    # a token slice (1/P of the wire bytes).
    mode = (dispatch.collective if dispatch is not None else None) or "nnz_ar"
    m_size = int(mesh.shape[max_])
    if mode == "nnz_rs" and t_local % m_size:
        raise ValueError(
            f"collective='nnz_rs' needs the local token count ({t_local}) "
            f"divisible by the model axis ({m_size})")
    out_spec = (P(tuple(dax) + (max_,), None) if mode == "nnz_rs"
                else P(dax, None))

    from ..sparse.distributed import shard_map

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(dax, None), P(), P(max_), P(max_), P(max_)),
        out_specs=(out_spec, P()),
    )
    def _sharded(x, router, wg, wi, wo):
        gates, probs = _route(cfg, x, router)  # (T_loc, E) all experts
        e_loc = wg.shape[0]
        m_idx = jax.lax.axis_index(max_)
        sl = m_idx * e_loc
        gates_loc = jax.lax.dynamic_slice(
            gates, (0, sl), (gates.shape[0], e_loc))
        part = _expert_ffn(cfg, x, wg, wi, wo, gates_loc, cap, use_pallas,
                           dispatch)
        if mode == "nnz_rs":
            out = jax.lax.psum_scatter(part, max_, scatter_dimension=0,
                                       tiled=True)
        else:
            out = jax.lax.psum(part, max_)  # atomic collective writeback
        aux = _aux_loss(cfg, gates, probs)
        aux = jax.lax.pmean(aux, dax) if dax else aux
        aux = jax.lax.pmean(aux, max_)
        return out.astype(x.dtype), aux

    return _sharded(x2d, p["router"], p["wg"], p["wi"], p["wo"])


# ---------------------------------------------------------------------------
# Empirical dispatch tuning (repro.tune.moe wired to this model)
# ---------------------------------------------------------------------------


def default_dispatch(cfg):
    """The static dispatch point ``apply_moe(dispatch=None)`` uses: the
    config's capacity factor with 128-wide tiles.  The tuner's baseline —
    a tuned schedule is never slower than this on the measured configs."""
    from ..tune.moe import MoeDispatchSchedule

    return MoeDispatchSchedule(capacity_factor=cfg.capacity_factor)


def expert_lengths_from_gates(gates) -> "jnp.ndarray":
    """Expert-segment histogram of a routing decision: routed tokens per
    expert from the dense (T, E) gate matrix (zeros off the top-k)."""
    return (gates > 0).sum(axis=0)


def balanced_expert_lengths(cfg, t_tokens: int):
    """The histogram a perfectly load-balanced router would produce —
    the tuning default when no observed routing is supplied."""
    import numpy as np

    total = t_tokens * cfg.experts_per_token
    base, extra = divmod(total, cfg.n_experts)
    lengths = np.full(cfg.n_experts, base, np.int64)
    lengths[:extra] += 1
    return lengths


def skewed_expert_lengths(cfg, t_tokens: int, *, a: float = 1.5,
                          seed: int = 0):
    """A Zipf-skewed routing histogram — the representative hot-expert
    workload the ``beyond/moe_tuner_gap`` benchmark tunes."""
    import numpy as np

    rng = np.random.default_rng(seed)
    w = rng.zipf(a, cfg.n_experts).astype(np.float64)
    total = t_tokens * cfg.experts_per_token
    return np.maximum(w / w.sum() * total, 1).astype(np.int64)


def moe_tune_dispatch(cfg, t_tokens: int, *, expert_lengths=None,
                      cache=None, measure=None, warmup=None, iters=None,
                      backend=None, **kw):
    """Empirically tune this config's dispatch schedule for ``t_tokens``
    local tokens (``repro.tune.tune_moe_dispatch`` keyed by the
    expert-segment histogram).  ``expert_lengths`` is the observed
    routed-tokens-per-expert histogram (e.g.
    ``expert_lengths_from_gates``); default assumes balanced routing.
    Returns a :class:`~repro.tune.TuneResult` whose ``.schedule`` plugs
    into ``apply_moe(..., dispatch=...)``; a repeat call with the same
    histogram replays the per-backend cache with zero measurements.

    When no histogram is supplied the balanced assumption stands in —
    and capacity shrinking is withheld (the drop constraint is only
    trustworthy on *observed* routing; a sub-default capacity that is
    free on the balanced histogram drops tokens on a skewed live
    batch)."""
    import numpy as np

    from ..tune.moe import tune_moe_dispatch as _tune

    kw.setdefault("allow_capacity_shrink", expert_lengths is not None)
    kw.setdefault("max_tokens", t_tokens)
    if expert_lengths is None:
        expert_lengths = balanced_expert_lengths(cfg, t_tokens)
    return _tune(np.asarray(expert_lengths), cfg.d_model, cfg.moe_d_ff,
                 dtype=str(cfg.param_dtype), default=default_dispatch(cfg),
                 cache=cache, measure=measure, warmup=warmup, iters=iters,
                 backend=backend, **kw)


def moe_tune_collective(cfg, params, x2d, ctx, *, dispatch=None,
                        cache=None, measure=None, warmup=None, iters=None,
                        backend=None):
    """Tune the expert-parallel writeback collective on a *real* mesh
    (DESIGN.md §12): measures ``apply_moe`` end to end under each
    feasible mode ('nnz_ar' psum vs 'nnz_rs' psum_scatter, when the
    local token count divides the model axis) and persists the winner —
    a :class:`~repro.tune.MoeDispatchSchedule` carrying ``collective`` —
    under a mesh-extent-suffixed key, so replays are measurement-free
    and a different mesh re-tunes.  ``dispatch`` seeds the GEMM tiling
    (default: the config's static point); like the wire mode on SpMM,
    only the collective axis is searched here — the tiling axes belong
    to :func:`moe_tune_dispatch`.

    This lives at the models layer because the objective *is* the model
    op (``repro.tune`` never imports ``repro.models``)."""
    import jax as _jax

    from ..tune.cache import default_cache, fingerprint_from_lengths
    from ..tune.driver import _replay, drive
    from ..tune.measure import time_fn
    from ..tune.moe import moe_schedule_key
    from ..tune.space import CollectiveAxis, SearchContext, SearchSpace

    if ctx is None or ctx.mesh is None or ctx.model_axis is None:
        raise ValueError("moe_tune_collective needs a sharded ctx "
                         "(mesh + model_axis)")
    if cache is None:
        cache = default_cache(backend)
    base = (dispatch or default_dispatch(cfg)).replace(collective=None)
    m_size = int(ctx.mesh.shape[ctx.model_axis])
    t = int(x2d.shape[0])
    d_size = int(functools.reduce(
        lambda a, b: a * b, (ctx.mesh.shape[a] for a in ctx.data_axes), 1))
    t_local = t // d_size

    lengths = balanced_expert_lengths(cfg, t)
    fp = fingerprint_from_lengths(lengths, (cfg.n_experts, cfg.d_model), t)
    key = (f"moedist:{fp}|F{cfg.moe_d_ff}|{moe_schedule_key(base)}"
           f"|mesh:{m_size}")
    hit = _replay(cache, key)
    if hit is not None:
        return hit

    if measure is None:
        def measure(s):
            fn = _jax.jit(
                lambda xx: apply_moe(cfg, params, xx, ctx, dispatch=s)[0])
            return time_fn(fn, x2d, warmup=warmup, iters=iters)

    modes = ["nnz_ar"] + (["nnz_rs"] if t_local % m_size == 0 else [])
    space = SearchSpace((CollectiveAxis(modes),), key_fn=moe_schedule_key)
    ctx_s = SearchContext(axis_size=m_size, workload=lengths)
    return drive(space, ctx_s, cache=cache, key=key, measure=measure,
                 ranked=space.cross(ctx_s, [base]))


def moe_dispatch_schedule(cfg, t_tokens: int, *, expert_lengths=None,
                          cache=None, backend=None):
    """Measurement-free resolver: the tuned dispatch for this config's
    histogram if the cache has one, else the static default.  Safe on a
    serving path — never stalls on a tuning run.  Mirrors
    :func:`moe_tune_dispatch`'s keying: an assumed (``None``) histogram
    resolves only no-shrink records."""
    import numpy as np

    from ..tune.moe import moe_cached_or_default

    observed = expert_lengths is not None
    if expert_lengths is None:
        expert_lengths = balanced_expert_lengths(cfg, t_tokens)
    return moe_cached_or_default(np.asarray(expert_lengths), cfg.d_model,
                                 cfg.moe_d_ff, dtype=str(cfg.param_dtype),
                                 default=default_dispatch(cfg),
                                 cache=cache, backend=backend,
                                 allow_capacity_shrink=observed,
                                 max_tokens=t_tokens)
