"""Fusion IR — chains of ``{sparse op, monoid, epilogue}`` nodes over a
shared iteration space (DESIGN.md §10).

PR 4/5 landed fusions as hand-written instances: an :class:`Epilogue`
field on :class:`Schedule` here, a one-pass attention kernel there.
This module makes the *shape* of those fusions first-class:

* a :class:`FuseNode` is one op in a producer→consumer chain — a
  reducing kernel anchor (``spmm`` / ``grouped_matmul`` /
  ``segment_reduce``), a scatter ``combine``, elementwise ``ewise``
  work expressed as the :class:`~repro.core.Epilogue` it would fuse as,
  or a ``norm`` over all rows (:class:`BatchNorm`), which runs in XLA
  and ends a fold;
* a :class:`Launch` is one executable unit the planner emitted: an
  anchor node plus the chain members folded into its epilogue slot;
* a :class:`FusePlan` is the planner's output — the chain, its
  launches, the per-boundary :class:`FuseDecision`, and the legality
  reason for every split;
* :class:`FuseDecision` alone is what the tuner caches (``fuse:`` keys,
  ``TuneRecord`` kind tag ``"fuse"``): the fuse/split bit per chain
  boundary, replayable onto the same chain via
  :func:`repro.fuse.planner.plan`.

Nodes are *static* descriptions; array operands live in a parallel
per-node params list the executor consumes (``repro.fuse.execute``), so
chains are hashable, cache-keyable and reusable across inputs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from ..core.schedule import Epilogue, Schedule, as_schedule

__all__ = [
    "BatchNorm",
    "EPILOGUE_CAPABLE",
    "FuseDecision",
    "FuseNode",
    "FusePlan",
    "KINDS",
    "Launch",
    "PALLAS_KINDS",
    "chain_sig",
    "combine_node",
    "ewise",
    "gcn_chain",
    "grouped_matmul_node",
    "moe_expert_chain",
    "norm_node",
    "segment_reduce_node",
    "spmm_node",
]

KINDS = ("spmm", "grouped_matmul", "segment_reduce", "combine", "ewise",
         "norm")

#: kinds that execute as a Pallas kernel when they anchor a launch
#: (``combine`` is an XLA scatter, ``ewise`` an XLA elementwise pass,
#: ``norm`` an XLA pass over all rows)
PALLAS_KINDS = frozenset({"spmm", "grouped_matmul", "segment_reduce"})

#: anchors exposing the shared in-kernel epilogue slot — the targets of
#: the epilogue-fold planner rule.  ``ewise`` is included: an unfused
#: elementwise launch is its own epilogue template and absorbs further
#: elementwise work the same way a kernel's slot does.
EPILOGUE_CAPABLE = frozenset({"spmm", "grouped_matmul", "ewise"})

#: monoid vocabulary of the reducing kinds (mirrors
#: ``sparse.segment_reduce``'s ``op`` — 'mean' is the add monoid with a
#: fused count column; 'sum' is the add monoid)
REDUCE_OPS = ("sum", "max", "min", "mean")


@dataclasses.dataclass(frozen=True)
class BatchNorm:
    """Batch normalisation over the rows (Ioffe and Szegedy; the
    semantics of ``torch.nn.BatchNorm1d``): in training, each column
    normalised by its batch mean and biased variance, then scaled and
    shifted (affine), and the running statistics moved ``momentum`` of
    the way to the batch mean and the unbiased variance; in evaluation,
    normalised by the running statistics."""

    eps: float = 1e-5
    momentum: float = 0.1
    training: bool = True


@dataclasses.dataclass(frozen=True)
class FuseNode:
    """One chain node.  ``op`` is the reduction monoid name (reducing
    kinds only); ``epilogue`` is the node's own elementwise work — for
    ``ewise`` nodes it *is* the node, for anchors it is work requested
    at the node itself (usually noop; the planner folds downstream
    ``ewise`` nodes into it; a ``norm`` node's activation follows its
    normalisation).  ``schedule`` rides on ``spmm`` /
    ``segment_reduce`` anchors, ``norm`` on ``norm`` nodes."""

    kind: str
    op: str = "sum"
    epilogue: Epilogue = Epilogue()
    schedule: Optional[Schedule] = None
    label: str = ""
    norm: Optional[BatchNorm] = None

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown node kind {self.kind!r}; "
                             f"one of {KINDS}")
        if self.op not in REDUCE_OPS:
            raise ValueError(f"unknown reduction op {self.op!r}; "
                             f"one of {REDUCE_OPS}")
        if (self.kind == "norm") != (self.norm is not None):
            raise ValueError("a norm node, and only a norm node, carries "
                             "its BatchNorm")

    @property
    def tag(self) -> str:
        """Stable signature component (cache keys, error messages)."""
        parts = [self.kind]
        if self.kind in ("segment_reduce", "combine") or self.op != "sum":
            parts.append(self.op)
        if self.norm is not None:
            parts.append("batch" if self.norm.training else "batch.eval")
        if not self.epilogue.is_noop:
            parts.append(f"[{self.epilogue.tag}]")
        return ":".join(parts)


def spmm_node(schedule=None, *, epilogue: Epilogue = Epilogue(),
              label: str = "") -> FuseNode:
    """A scheduled SpMM anchor (``out = A @ X`` — optionally ``A @ (X W)``
    when the executor params carry a dense ``w``)."""
    sched = None if schedule is None else as_schedule(schedule)
    return FuseNode("spmm", epilogue=epilogue, schedule=sched, label=label)


def grouped_matmul_node(*, epilogue: Epilogue = Epilogue(),
                        label: str = "") -> FuseNode:
    """An expert-grouped GEMM anchor (``kernels.ops.grouped_matmul``)."""
    return FuseNode("grouped_matmul", epilogue=epilogue, label=label)


def segment_reduce_node(op: str = "sum", *, schedule=None,
                        label: str = "") -> FuseNode:
    """A grouped segment-reduce anchor under the named monoid, with an
    optional explicit :class:`Schedule`."""
    sched = None if schedule is None else as_schedule(schedule)
    return FuseNode("segment_reduce", op=op, schedule=sched, label=label)


def norm_node(norm: BatchNorm = BatchNorm(), *,
              activation: Optional[str] = None, label: str = "") -> FuseNode:
    """A batch norm over all rows, then ``activation``: an XLA launch of
    its own (its statistics need every row, which no kernel's per-block
    epilogue sees).  Its params may carry a ``keep`` mask (dropout's 0 or
    1/(1-p)), applied after the activation."""
    return FuseNode("norm", epilogue=Epilogue(activation=activation),
                    norm=norm, label=label)


def combine_node(op: str = "sum", *, label: str = "") -> FuseNode:
    """The MoE combine scatter: gate-weighted token writeback under the
    named monoid ('sum' / 'min' / 'mean')."""
    return FuseNode("combine", op=op, label=label)


def ewise(activation: Optional[str] = None, *, bias: bool = False,
          residual: bool = False, out_dtype: Optional[str] = None,
          label: str = "") -> FuseNode:
    """Elementwise chain work, expressed as the Epilogue it would fuse
    as: ``cast(act(x + bias) + residual)``."""
    return FuseNode("ewise", label=label,
                    epilogue=Epilogue(activation=activation, bias=bias,
                                      residual=residual,
                                      out_dtype=out_dtype))


@dataclasses.dataclass(frozen=True)
class FuseDecision:
    """The planner's per-boundary choice — ``fused[i]`` says whether the
    boundary between ``chain[i]`` and ``chain[i+1]`` fused.  This is the
    tunable, cacheable part of a plan (``TuneRecord`` kind ``"fuse"``)."""

    fused: Tuple[bool, ...]

    @property
    def tag(self) -> str:
        """Compact chain signature: one F(used)/S(tandalone) per node."""
        return "".join("F" if b else "S" for b in self.fused) or "-"


@dataclasses.dataclass(frozen=True)
class Launch:
    """One executable unit: ``anchor`` runs with ``epilogue`` fused onto
    its output block; ``members`` are the chain indices folded in
    (anchor first)."""

    anchor: FuseNode
    anchor_idx: int
    epilogue: Epilogue
    members: Tuple[int, ...]

    @property
    def is_pallas(self) -> bool:
        """True when the anchor lowers to a Pallas kernel (fusible)."""
        return self.anchor.kind in PALLAS_KINDS


@dataclasses.dataclass(frozen=True)
class FusePlan:
    """Planner output.  ``reasons[i]`` is empty when boundary ``i``
    fused, else the legality (or decision) reason it split."""

    chain: Tuple[FuseNode, ...]
    launches: Tuple[Launch, ...]
    decision: FuseDecision
    reasons: Tuple[str, ...]

    @property
    def n_launches(self) -> int:
        """Pallas kernel launches this plan executes (XLA elementwise /
        scatter launches are not counted — they are what fusion into a
        kernel epilogue *removes*)."""
        return sum(1 for ln in self.launches if ln.is_pallas)


def chain_sig(chain) -> str:
    """Stable chain signature for ``fuse:`` cache keys."""
    return ">".join(n.tag for n in chain)


# ---------------------------------------------------------------------------
# Chain builders for the landed fusions (each returns (chain, params)
# ready for plan() / execute.run_plan()).
# ---------------------------------------------------------------------------


def gcn_chain(adj, weights, biases=None, *, activation: str = "relu",
              final_activation: Optional[str] = None, schedule=None,
              norm: Optional[BatchNorm] = None, norms=None, keeps=None):
    """An L-layer GCN — layer ``i`` is ``Ã (H Wᵢ) + bᵢ`` — as a chain:
    per layer an ``spmm`` anchor and an ``ewise`` with its bias, between
    layers ``activation``, and after the last ``final_activation``.
    The planner folds each ewise into its producing SpMM's epilogue, so
    the model runs in L Pallas launches.

    Two layers without ``norm`` is Kipf and Welling's model, the 4-node
    chain ``spmm → ewise(act, bias) → spmm [→ ewise]``.  With ``norm``,
    each hidden layer's bias ewise is followed by a ``norm`` node
    (:func:`norm_node`, labelled ``gcn.layer<i>``) that normalises, then
    applies ``activation`` and the layer's dropout mask: the fold ends
    there, and the norm runs in XLA between launches (OGB's GCN: conv →
    BatchNorm → ReLU → dropout).  ``norms[i]`` holds hidden layer i's
    ``gamma``, ``beta`` and running ``mean``, ``var``; ``keeps[i]`` its
    mask or None.

    ``weights`` is ``(w0, ..., w_{L-1})``; ``biases`` optionally one a
    layer (a ``None`` entry drops that bias).  Returns ``(chain,
    params)``.
    """
    n_layers = len(weights)
    biases = biases if biases is not None else (None,) * n_layers
    chain, params = [], []
    for i, (w, b) in enumerate(zip(weights, biases)):
        chain.append(spmm_node(schedule, label=f"gcn{i}"))
        params.append({"a": adj, "w": w})
        if i < n_layers - 1 and norm is None:
            chain.append(ewise(activation, bias=b is not None,
                               label=f"gcn{i}.ep"))
            params.append({"bias": b})
        elif i < n_layers - 1:
            if b is not None:
                chain.append(ewise(bias=True, label=f"gcn{i}.ep"))
                params.append({"bias": b})
            chain.append(norm_node(norm, activation=activation,
                                   label=f"gcn.layer{i}"))
            params.append({**norms[i],
                           "keep": None if keeps is None else keeps[i]})
        elif final_activation is not None or b is not None:
            chain.append(ewise(final_activation, bias=b is not None,
                               label=f"gcn{i}.ep"))
            params.append({"bias": b})
    return tuple(chain), params


def moe_expert_chain(tile_experts, weights, bias=None, *,
                     activation: str = "silu",
                     out_dtype: Optional[str] = None,
                     token_tile: int = 128, f_tile: int = 128,
                     d_tile: int = 128):
    """The MoE expert up-projection — ``act(x @ W[e] + b[e])`` — as a
    2-node chain ``grouped_matmul → ewise``.  Fused, the activation (and
    per-expert bias / output cast) runs on the GEMM's output block: one
    Pallas launch per token tile instead of a GEMM pass plus an XLA
    elementwise pass.  Returns ``(chain, params)``.
    """
    chain = (grouped_matmul_node(label="expert_gemm"),
             ewise(activation, bias=bias is not None, out_dtype=out_dtype,
                   label="expert_gemm.ep"))
    params = [{"tile_experts": tile_experts, "weights": weights,
               "token_tile": token_tile, "f_tile": f_tile,
               "d_tile": d_tile},
              {"bias": bias}]
    return chain, params
