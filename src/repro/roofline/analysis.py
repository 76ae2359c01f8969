"""Byte models of the sparse ops, and the collective bytes of a compiled
module to check them against.

``predict_spmm_arg_bytes`` / ``predict_spmm_traffic_bytes`` model an EB
SpMM's argument footprint and HBM traffic; ``predict_collective_bytes``
/ ``predict_attention_collective_bytes`` the collective result bytes a
distributed reduction should compile to.  :func:`collective_bytes`
parses those from the compiled (per-device) HLO text: result-shape
bytes of every all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute.
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def dtype_itemsize(dt) -> int:
    """Bytes per element of ``dt`` — an HLO short name ('bf16',
    'f8e4m3fn') or anything ``np.dtype`` accepts (jax/numpy dtypes,
    'bfloat16' via the ml_dtypes registration jax ships)."""
    if isinstance(dt, str) and dt in _DTYPE_BYTES:
        return _DTYPE_BYTES[dt]
    import numpy as np

    return int(np.dtype(dt).itemsize)


def predict_spmm_arg_bytes(lanes: int, n_cols: int, n_dense_cols: int, *,
                           value_dtype=None, scales_rows: int = 0,
                           index_bytes: int = 4) -> int:
    """Modeled per-call argument bytes of the EB SpMM measurement program
    (``tune.measure.make_eb_runner``): two index streams over the
    ``lanes`` padded/grouped nonzeros, the value stream at the *storage*
    width of ``value_dtype`` (DESIGN.md §13, post-fp8-fallback), and the
    dense ``(n_cols, n_dense_cols)`` operand at the *operand* width —
    plus f32 per-row scales when the int8 quantized path adds them.

    This is the number ``memory_analysis().argument_size_in_bytes``
    reads back from the compiled runner, and the 'modeled bytes' the
    ``beyond/lowprec_spmm`` bench reports: a bf16 schedule should show
    ~2x fewer bytes than f32 on the same pattern.
    """
    from ..core.dtypes import operand_itemsize, value_itemsize

    total = lanes * (2 * index_bytes + value_itemsize(value_dtype))
    total += n_cols * n_dense_cols * operand_itemsize(value_dtype)
    total += scales_rows * 4
    return int(total)


def predict_spmm_traffic_bytes(lanes: int, n_rows: int,
                               n_dense_cols: int, *, value_dtype=None,
                               scales_rows: int = 0,
                               index_bytes: int = 4) -> int:
    """Modeled HBM traffic of one EB SpMM call — the bandwidth-bound
    roofline term the dtype axis moves (DESIGN.md §13).

    Unlike :func:`predict_spmm_arg_bytes` (argument footprint) this
    counts the *streams*: index + value lanes once, the gathered dense
    rows once per lane (``lanes * n_dense_cols`` elements at the
    operand width — the dominant term, and the one a narrow dtype
    halves), and the f32 output write.  ``modeled_speedup = f32_bytes /
    narrow_bytes`` is what a bandwidth-bound backend realizes; XLA-CPU
    wall clock does not track it (scalar bf16 converts), which is why
    the ``beyond/lowprec_spmm`` bench reports both."""
    from ..core.dtypes import operand_itemsize, value_itemsize

    total = lanes * (2 * index_bytes + value_itemsize(value_dtype))
    total += lanes * n_dense_cols * operand_itemsize(value_dtype)  # gather
    total += n_rows * n_dense_cols * 4  # f32 output
    total += scales_rows * 4
    return int(total)

_COLL_RE = re.compile(
    r"=\s*(?P<types>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<start>-start)?\(")
_SHAPE_RE = re.compile(r"(?P<dt>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")


def _shape_bytes(types: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(types):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-op-type result bytes of every collective in the (per-device)
    compiled module. '-done' ops are skipped (async pair double-count)."""
    out: dict = {}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _COLL_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        b = _shape_bytes(m.group("types"))
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += b
    return out


def predict_collective_bytes(collective, out_shape, *, axis_size: int,
                             itemsize: int = 4) -> int:
    """Per-device collective result bytes a distributed reduction op
    should compile to under ``collective`` (DESIGN.md §12) — the number
    :func:`collective_bytes` reads back from the compiled HLO.

    'row' (and ``None``) move nothing; 'nnz_ar' all-reduces the full
    ``out_shape`` partial on every device; 'nnz_rs' reduce-scatters it,
    so each device's collective *result* is the 1/P row slice it
    finalizes — 1/P of the all-reduce bytes on the wire per shard.  A
    1-member axis compiles its collectives away (0 bytes).
    """
    if axis_size <= 1 or collective in (None, "row"):
        return 0
    full = itemsize
    for d in out_shape:
        full *= int(d)
    if collective == "nnz_ar":
        return full
    if collective == "nnz_rs":
        return full // axis_size
    raise ValueError(f"unknown collective {collective!r}")


def predict_attention_collective_bytes(collective, *, n_heads: int,
                                       n_rows: int, dv_pad: int,
                                       axis_size: int,
                                       itemsize: int = 4) -> int:
    """Collective result bytes of one distributed fused-attention combine
    (``repro.sparse.dist_attention_shard_map``): the (H, R) row-max pmax
    is always a full all-reduce; the weighted l and accumulator — (H, R)
    and (H, R, dv_pad) — combine per ``collective`` like SpMM partials.
    """
    if axis_size <= 1 or collective in (None, "row"):
        return 0
    stats = n_heads * n_rows * itemsize  # pmax on m: always all-reduce
    lw_acc = n_heads * n_rows * (dv_pad + 1) * itemsize
    if collective == "nnz_rs":
        lw_acc //= axis_size
    elif collective != "nnz_ar":
        raise ValueError(f"unknown collective {collective!r}")
    return stats + lw_acc
