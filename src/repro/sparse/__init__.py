"""repro.sparse — the single public sparse API.

Formats (`CSR`, `COO`, `GroupedCOO`, `BlockedCOO`, `ELL`), generators (`random_csr`,
`power_law_csr`, `graph_pattern_csr`),
the unified ops (`spmm`, `sddmm`, `segment_reduce`, `sparse_attention`,
all taking ``schedule=``), and the scheduling surface re-exported from
core (`Schedule`, `Epilogue`, `register_strategy`).
"""
from ..core.schedule import (  # noqa: F401
    Epilogue,
    Schedule,
    as_schedule,
    available_strategies,
    register_strategy,
)
from .distributed import (  # noqa: F401
    COLLECTIVES,
    dist_attention_shard_map,
    dist_spmm,
    partition_nnz_coo,
    partition_rows_coo,
    shard_nnz_counts,
    spmm_shard_map,
)
from .formats import (  # noqa: F401
    COO,
    BlockedCOO,
    CSR,
    ELL,
    GroupedCOO,
    QuantizedCSR,
    dequantize,
    quantize_csr,
)
from .ops import sddmm, segment_reduce, sparse_attention, spmm  # noqa: F401
from .random import (  # noqa: F401
    GRAPH_PATTERNS,
    graph_pattern_csr,
    matrix_stats,
    power_law_csr,
    random_coo,
    random_csr,
)
