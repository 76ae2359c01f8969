"""``spmm_kernel_ms``: device time per step of the SpMM kernel launches
(the compiled step's Pallas launches that run ``spmm_eb`` or
``spmm_rb``, by their HLO)."""

from bench import trace


def read(rec):
    """Milliseconds per step, or None where no SpMM kernel ran."""
    tr = rec["trace"]
    spmm = trace.spmm_seconds(tr, trace.pallas_launches(rec["hlo"])) if tr else 0.0
    if spmm <= 0.0:
        return None
    return spmm / rec["steps"] * 1e3
