"""``xla_ms``: device time per step of the operations that are not Pallas
kernels (dense matmuls, the SpMM's XLA backward, dropout, optimizer)."""

from bench import trace


def read(rec):
    """Milliseconds per step, or None without a trace."""
    tr = rec["trace"]
    if not tr:
        return None
    return trace.xla_seconds(tr, trace.pallas_launches(rec["hlo"])) / rec["steps"] * 1e3
