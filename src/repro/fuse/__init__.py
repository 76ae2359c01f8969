"""``repro.fuse`` — the sparse fusion IR and planner (DESIGN.md §10).

Every fusion in the library now goes through one pipeline::

    chain = [spmm_node(), ewise("relu", bias=True), spmm_node()]
    p     = plan(chain)            # legality + greedy fusion
    out   = run_plan(p, x, params) # ≤2 Pallas launches for this chain
    tune_plan(chain, x, params)    # measure fused-vs-split, cache it

The IR (:mod:`~repro.fuse.ir`) describes chains of
``{sparse op, monoid, epilogue}`` nodes; the rule registry
(:mod:`~repro.fuse.rules`) decides per boundary whether a consumer may
fold into the producer's launch (``core.Epilogue`` and the monoid
registry are the rules' targets); the planner
(:mod:`~repro.fuse.planner`) emits launches and the tuner measures
fuse-vs-split, fingerprint-keyed like every other schedule cache.
"""
from .execute import moe_combine, run_chain_ref, run_plan
from .ir import (
    EPILOGUE_CAPABLE,
    PALLAS_KINDS,
    BatchNorm,
    FuseDecision,
    FuseNode,
    FusePlan,
    Launch,
    chain_sig,
    combine_node,
    ewise,
    gcn_chain,
    grouped_matmul_node,
    moe_expert_chain,
    norm_node,
    segment_reduce_node,
    spmm_node,
)
from .legality import can_fuse
from .planner import plan, plan_key, split_all, tune_plan, tuned_plan
from .rules import available_rules, register_rule, unregister_rule

__all__ = [
    "EPILOGUE_CAPABLE",
    "PALLAS_KINDS",
    "BatchNorm",
    "FuseDecision",
    "FuseNode",
    "FusePlan",
    "Launch",
    "available_rules",
    "can_fuse",
    "chain_sig",
    "combine_node",
    "ewise",
    "gcn_chain",
    "grouped_matmul_node",
    "moe_combine",
    "moe_expert_chain",
    "norm_node",
    "plan",
    "plan_key",
    "register_rule",
    "run_chain_ref",
    "run_plan",
    "segment_reduce_node",
    "split_all",
    "spmm_node",
    "tune_plan",
    "tuned_plan",
    "unregister_rule",
]
