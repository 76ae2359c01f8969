"""Operations and compulsory bytes of the cells beside GCN training, from
logical shapes alone: the additive-score attention of GAT training, the
GAT training step's model operations, and the GCN forward of inference.

As in ``bench.counts``, nothing here looks at padded lanes, tiles or a
kernel's own streams: the counts read the same work whatever implements
it.  A kernel change moves the measured time, never the count.
"""
from __future__ import annotations

from bench.counts import F32, I32, least_time_s, spmm_flops

#: the fused attention kernels, as the program names their launches
ATTN_KERNELS = ("fused_attention_fwd", "fused_attention_bwd")
#: operations an entry and head of the forward costs besides its
#: weighted values: the score's add, the LeakyReLU (a compare and a
#: multiply), the max, the shift and exponential, the denominator's add,
#: the mask's multiply
FWD_OPS = 7
#: the same of the backward: the recomputed score and weight (add,
#: LeakyReLU, shift, exponential, scale by 1/l), the mask on the weight
#: and on its gradient, the softmax backward (a subtract and a multiply),
#: the LeakyReLU's derivative, and the two scatters of the score terms'
#: gradients
BWD_OPS = 12


def attn_layers(cfg: dict) -> list[dict]:
    """The logical attention of each GAT layer: heads and value width."""
    return [{"heads": cfg["heads"], "width": cfg["hidden"]},
            {"heads": cfg["out_heads"], "width": cfg["n_classes"]}]


def attn_fwd_flops(n_entries: int, heads: int, width: int) -> int:
    """Forward: per entry and head, :data:`FWD_OPS` and a multiply and an
    add per value feature."""
    return n_entries * heads * (FWD_OPS + 2 * width)


def attn_bwd_flops(n_entries: int, heads: int, width: int) -> int:
    """Backward: per entry and head, :data:`BWD_OPS`, the value gradient's
    and the weight gradient's multiply and add per value feature."""
    return n_entries * heads * (BWD_OPS + 4 * width)


def attn_fwd_bytes(n_nodes: int, n_entries: int, heads: int,
                   width: int) -> int:
    """Forward traffic: each entry's row and column index, the per-node
    score terms s and t, the values once, the output once, the row
    statistics (max and denominator), and the (entries, heads) mask."""
    return (n_entries * 2 * I32 + 2 * n_nodes * heads * F32
            + 2 * n_nodes * heads * width * F32 + 2 * n_nodes * heads * F32
            + n_entries * heads * F32)


def attn_bwd_bytes(n_nodes: int, n_entries: int, heads: int,
                   width: int) -> int:
    """Backward traffic: the indices, the score terms and their
    gradients, the values and their gradient, the output's gradient, the
    row statistics (max, denominator and the softmax backward's row dot),
    and the mask."""
    return (n_entries * 2 * I32 + 4 * n_nodes * heads * F32
            + 3 * n_nodes * heads * width * F32 + 3 * n_nodes * heads * F32
            + n_entries * heads * F32)


def attn_least_time_s(cfg: dict, peak: dict) -> float:
    """The least time of a training step's attention: each layer's forward
    and backward, each at the larger of its operations and its bytes."""
    n, nnz = cfg["n_nodes"], cfg["n_entries"]
    total = 0.0
    for lay in attn_layers(cfg):
        h, w = lay["heads"], lay["width"]
        total += least_time_s(attn_fwd_flops(nnz, h, w),
                              attn_fwd_bytes(n, nnz, h, w), peak)[0]
        total += least_time_s(attn_bwd_flops(nnz, h, w),
                              attn_bwd_bytes(n, nnz, h, w), peak)[0]
    return total


def gat_train_flops(cfg: dict) -> int:
    """Model operations of one full-batch GAT training step: each layer's
    projection x W, its score terms a_l·Wh and a_r·Wh, and its attention
    (:func:`attn_fwd_flops`); backward, the attention
    (:func:`attn_bwd_flops`), the score terms' and the projection's
    weight gradients, and the first layer's input gradient from the
    second.  Elementwise work outside the attention (ELU, dropout of the
    features, loss, optimizer) is not counted."""
    n, nnz, f = cfg["n_nodes"], cfg["n_entries"], cfg["n_features"]
    mm = lambda m, k, p: 2 * m * k * p  # noqa: E731
    total, f_in = 0, f
    for i, lay in enumerate(attn_layers(cfg)):
        hw = lay["heads"] * lay["width"]
        fwd = mm(n, f_in, hw) + 2 * mm(n, 1, hw) + attn_fwd_flops(
            nnz, lay["heads"], lay["width"])
        bwd = (attn_bwd_flops(nnz, lay["heads"], lay["width"])
               + 2 * mm(n, 1, hw)       # score terms into dWh
               + 2 * mm(1, n, hw)       # d a_l, d a_r
               + mm(f_in, n, hw)        # dW = x^T dWh
               + (mm(n, hw, f_in) if i else 0))  # dx of the second layer
        total += fwd + bwd
        f_in = hw
    return total


def gcn_infer_flops(cfg: dict) -> int:
    """Model operations of one two-layer GCN forward: X W0, A (X W0),
    H W1, A (H W1)."""
    n, nnz = cfg["n_nodes"], cfg["n_entries"]
    f, h, c = cfg["n_features"], cfg["hidden"], cfg["n_classes"]
    return (2 * n * f * h + spmm_flops(nnz, h) + 2 * n * h * c
            + spmm_flops(nnz, c))


def attn_launches(hlo_text: str) -> list[dict]:
    """The fused attention launches among a compiled program's Pallas
    launches (``bench.trace.pallas_launches``), in program order: ``name``
    (the instruction name its trace events carry, which the kernel's
    ``name=`` begins) and ``kernel``, which of :data:`ATTN_KERNELS` it
    runs."""
    from bench import trace

    out = []
    for lc in trace.pallas_launches(hlo_text):
        kernel = next((k for k in ATTN_KERNELS if lc["name"].startswith(k)), None)
        if kernel:
            out.append({"name": lc["name"], "kernel": kernel})
    return out
