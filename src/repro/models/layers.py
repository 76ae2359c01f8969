"""Shared neural-net layers (functional, pytree params)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seq_shard(cfg, x, axis: int = 1):
    """Megatron-SP constraint: pin the sequence dim to the 'model' mesh
    axis (no-op unless cfg.seq_parallel_attn; requires an ambient mesh)."""
    if not getattr(cfg, "seq_parallel_attn", False):
        return x
    from jax.sharding import PartitionSpec as P

    u = P.UNCONSTRAINED
    spec = [u] * x.ndim
    spec[axis] = "model"
    return jax.lax.with_sharding_constraint(x, P(*spec))


def seq_unshard(cfg, x, axis: int = 1):
    """Force the sequence dim unsharded (the K/V all-gather of
    seq-parallel attention)."""
    if not getattr(cfg, "seq_parallel_attn", False):
        return x
    from jax.sharding import PartitionSpec as P

    u = P.UNCONSTRAINED
    spec = [u] * x.ndim
    spec[axis] = None
    return jax.lax.with_sharding_constraint(x, P(*spec))

# ---------------------------------------------------------------- norms


def rmsnorm(x, scale, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def init_norm(cfg, d):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros((d,), cfg.param_dtype)}
    return {"scale": jnp.ones((d,), cfg.param_dtype),
            "bias": jnp.zeros((d,), cfg.param_dtype)}


def apply_norm(cfg, p, x):
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


# ------------------------------------------------------- fused sparse


def gcn_layer(adj, x, w, b=None, *, activation="relu", residual=None,
              schedule="auto"):
    """One GCN layer, fused: ``act(Ã (x @ w) + b) [+ residual]`` runs as a
    *single* scheduled SpMM kernel with an in-kernel epilogue
    (DESIGN.md §8) instead of three HBM passes (spmm → bias-add → act).
    Differentiable in ``x``/``w``/``b``/``residual`` through the sparse
    custom VJP."""
    from ..core.schedule import Epilogue
    from ..sparse import spmm

    ep = Epilogue(activation=activation, bias=b is not None,
                  residual=residual is not None)
    return spmm(adj, x @ w, schedule=schedule, bias=b, residual=residual,
                epilogue=ep)


def gcn(adj, x, params, *, norm=None, stats=None, keeps=None,
        activation="relu", final_activation=None, schedule=None, plan=None):
    """An L-layer GCN, ``L`` the number of weights ``w0 .. w{L-1}`` in
    ``params``: layer ``i`` is ``Ã (H Wᵢ) + bᵢ`` (``b<i>`` optional),
    with ``activation`` between layers and ``final_activation`` after the
    last.  Built as a ``repro.fuse`` chain (``gcn_chain``) and executed by
    the fusion planner: each bias (and, without a norm, each activation)
    folds into its SpMM's epilogue, so the model runs in L Pallas
    launches.

    ``norm``, a :class:`repro.fuse.BatchNorm`, puts a batch norm after
    each hidden layer, then the activation, then the dropout mask
    ``keeps["keep<i>"]`` (0 or 1/(1-p), optional): OGB's GCN (conv →
    BatchNorm → ReLU → dropout).  The norm runs in XLA between the
    launches, under ``gcn.layer<i>`` with its children ``norm`` and
    ``act``.  It takes ``params["gamma<i>"]`` and ``["beta<i>"]`` and
    the running ``stats["mean<i>"]`` and ``["var<i>"]`` (in training,
    zeros and ones where ``stats`` is None).

    Returns ``(logits, stats)``: with a norm, the running statistics it
    leaves (moved by the batch in training, as given in evaluation);
    ``{}`` without.  ``plan`` overrides the greedy plan; ``schedule``
    rides on every SpMM anchor (``None`` → per-matrix auto selection).
    Differentiable in ``x`` and the parameters through the planned
    launches' custom VJPs."""
    from ..fuse import gcn_chain
    from ..fuse import plan as plan_chain
    from ..fuse import run_plan

    n_layers = sum(1 for k in params if k.startswith("w"))
    weights = tuple(params[f"w{i}"] for i in range(n_layers))
    biases = tuple(params.get(f"b{i}") for i in range(n_layers))
    norms = None
    if norm is not None:
        norms = []
        for i in range(n_layers - 1):
            width = weights[i].shape[1]
            norms.append({
                "gamma": params[f"gamma{i}"], "beta": params[f"beta{i}"],
                "mean": (jnp.zeros((width,), jnp.float32) if stats is None
                         else stats[f"mean{i}"]),
                "var": (jnp.ones((width,), jnp.float32) if stats is None
                        else stats[f"var{i}"])})
        keeps = [None if keeps is None else keeps.get(f"keep{i}")
                 for i in range(n_layers - 1)]
    elif keeps:
        raise ValueError("the dropout between layers runs in the norm "
                         "launch: keeps need a norm")
    chain, chain_params = gcn_chain(
        adj, weights, biases, activation=activation,
        final_activation=final_activation, schedule=schedule, norm=norm,
        norms=norms, keeps=keeps)
    p = plan_chain(chain) if plan is None else plan
    running = {}
    logits = run_plan(p, x, chain_params, stats=running)
    out = {}
    for i in range(n_layers - 1):
        if f"gcn.layer{i}" in running:
            out[f"mean{i}"], out[f"var{i}"] = running[f"gcn.layer{i}"]
    return logits, out


def gcn_two_layer(adj, x, w0, w1, b0=None, b1=None, *,
                  activation="relu", final_activation=None, schedule=None,
                  plan=None):
    """Two-layer GCN — ``Ã act(Ã (x @ w0) + b0) @ w1 [+ b1]`` — the
    L = 2 case of :func:`gcn` with no norm and no dropout: the
    activations/biases fold into their producing SpMM's epilogue, so the
    whole model is **2 Pallas launches** (DESIGN.md §10).

    ``plan`` overrides the greedy plan (e.g. a
    :func:`repro.fuse.tuned_plan` replay or an explicit split for A/B
    timing); ``schedule`` rides on both SpMM anchors (``None`` →
    per-matrix auto selection).  Differentiable in ``x``/weights/biases
    through the planned launches' custom VJPs."""
    params = {"w0": w0, "w1": w1, "b0": b0, "b1": b1}
    return gcn(adj, x, params, activation=activation,
               final_activation=final_activation, schedule=schedule,
               plan=plan)[0]


def gat_layer(pattern, x, w, a_l, a_r, b, *, concat: bool = True,
              activation=None, slope: float = 0.2, input_keep=None,
              coef_keep=None, schedule=None):
    """One multi-head graph attention layer (Veličković et al., ICLR 2018,
    §2.1): per head h, ``Wh = x W_h``, scores ``LeakyReLU(a_l·Wh_i +
    a_r·Wh_j)`` over each row's pattern entries, softmax, then the
    coefficient-weighted sum of ``Wh_j``; heads concatenated (``concat``)
    or averaged, then the bias and ``activation``.

    pattern     ``(rows, cols, n_rows)``: the adjacency's entries (self
                loops included); only the pattern is attended over.
    x           (n, F_in); w (F_in, H·F); a_l, a_r (H, F); b (H·F,) when
                concatenated, (F,) when averaged.
    input_keep  optional (n, F_in) dropout mask on ``x`` (0 or 1/(1-p));
    coef_keep   optional (nnz, H) dropout mask on the normalised
                coefficients, applied in the fused kernels.

    The attention runs through ``graph_attention`` with the additive
    score: the fused forward and backward kernels, all heads in one
    launch each.  XLA work is named ``proj``, ``scores`` and ``out``."""
    from .attention import graph_attention

    n_heads, width = a_l.shape
    if input_keep is not None:
        x = x * input_keep
    with jax.named_scope("proj"):
        wh = (x @ w).reshape(x.shape[0], n_heads, width)
    with jax.named_scope("scores"):
        s = jnp.einsum("nhf,hf->nh", wh, a_l)[..., None]
        t = jnp.einsum("nhf,hf->nh", wh, a_r)[..., None]
    out = graph_attention(pattern, s, t, wh, schedule=schedule,
                          score="additive", slope=slope, keep=coef_keep)
    with jax.named_scope("out"):
        out = (out.reshape(out.shape[0], n_heads * width) if concat
               else jnp.mean(out, axis=1)) + b
        return out if activation is None else activation(out)


def gat_two_layer(pattern, x, params, *, slope: float = 0.2, keeps=None,
                  schedule=None):
    """The two-layer GAT of Veličković et al. (transductive setting): a
    concatenating layer with ELU, then an averaging output layer; returns
    the logits (n, C).  ``params`` holds ``w0, al0, ar0, b0`` and ``w1,
    al1, ar1, b1`` (shapes as :func:`gat_layer`); ``keeps`` optional
    dropout masks ``x0``, ``coef0``, ``x1``, ``coef1`` (the input and
    coefficient masks of each layer; absent in evaluation).  The layers'
    XLA work is named ``gat.layer0`` and ``gat.layer1``."""
    keeps = keeps or {}
    h = x
    for i, (concat, act) in enumerate(((True, jax.nn.elu), (False, None))):
        with jax.named_scope(f"gat.layer{i}"):
            h = gat_layer(pattern, h, params[f"w{i}"], params[f"al{i}"],
                          params[f"ar{i}"], params[f"b{i}"], concat=concat,
                          activation=act, slope=slope,
                          input_keep=keeps.get(f"x{i}"),
                          coef_keep=keeps.get(f"coef{i}"), schedule=schedule)
    return h


# ---------------------------------------------------------------- linear


def dense(x, w, b=None):
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def init_dense(key, d_in, d_out, dtype, bias=False, scale=None):
    if scale is None:
        scale = d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def apply_dense(p, x):
    return dense(x, p["w"], p.get("b"))


# ---------------------------------------------------------------- rope


def rope_freqs(dh: int, theta: float):
    return theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)


def apply_rope(x, positions, theta: float = 1e4):
    """x: (B, S, H, Dh); positions: (S,) or (B, S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (dh/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- mlp


def init_mlp(cfg, key, d_model=None, d_ff=None):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp_type == "swiglu":
        return {
            "wi": init_dense(k1, d, f, cfg.param_dtype)["w"],
            "wg": init_dense(k2, d, f, cfg.param_dtype)["w"],
            "wo": init_dense(k3, f, d, cfg.param_dtype, scale=f ** -0.5)["w"],
        }
    return {
        "wi": init_dense(k1, d, f, cfg.param_dtype)["w"],
        "wo": init_dense(k3, f, d, cfg.param_dtype, scale=f ** -0.5)["w"],
    }


def apply_mlp(cfg, p, x):
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(dense(x, p["wg"])) * dense(x, p["wi"])
    else:
        h = jax.nn.gelu(dense(x, p["wi"]))
    return dense(h, p["wo"])


# ---------------------------------------------------------------- embed / loss


def init_embedding(key, vocab, d, dtype):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


def embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed(table, x):
    return jnp.einsum("...d,vd->...v", x, table)


def cross_entropy_loss(logits, labels, mask=None):
    """Mean token NLL. logits (..., V) f32-upcast; labels int."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    return _masked_mean(nll, mask)


def _masked_mean(nll, mask):
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def lm_loss_from_features(table, x, labels, mask=None):
    """Vocab-sharding-friendly LM loss from final features.

    Avoids gathering the full (B, S, V) logits across the vocab shards:
    logsumexp reduces the sharded logits in place (psum under SPMD) and
    the gold logit is recomputed as <x, E[label]> — a label-row gather of
    the embedding table instead of a label-column gather of the logits
    (the latter forced a 20-40 GB/chip all-gather + f32 copy at 152k
    vocab).
    """
    logits = unembed(table, x).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)  # (B, S)
    gold_emb = jnp.take(table, labels, axis=0)  # (B, S, D)
    gold = jnp.einsum("bsd,bsd->bs", x.astype(jnp.float32),
                      gold_emb.astype(jnp.float32))
    return _masked_mean(logz - gold, mask)
