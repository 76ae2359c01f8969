"""``spmm_bwd_ms``: device time per step of the SpMM's backward in XLA,
the operations under the program's scope ``spmm.bwd`` (the recompute of
the pre-activation, the activation's VJP, the sampled product, the
transpose SpMM and the bias gradient)."""

from bench import attribution, trace


def read(rec):
    """Milliseconds per step, or None without a trace or where the
    program names no ``spmm.bwd``."""
    tr = rec["trace"]
    if not tr:
        return None
    by_scope = attribution.xla_by_scope(tr, rec["hlo"], trace.pallas_launches(rec["hlo"]))
    bwd = attribution.spmm_bwd_seconds(by_scope)
    if bwd <= 0.0:
        return None
    return bwd / rec["steps"] * 1e3
