"""``attn_kernel_ms``: device time per step of the fused attention kernel
launches (the compiled step's Pallas launches that run
``fused_attention_fwd`` or ``fused_attention_bwd``, by their HLO)."""

from bench import counts_gat


def read(rec):
    """Milliseconds per step, or None without a trace or where no
    attention kernel ran."""
    tr = rec["trace"]
    if not tr:
        return None
    launches = counts_gat.attn_launches(rec["hlo"])
    attn = sum(tr["ops"].get(lc["name"], 0.0) for lc in launches)
    if attn <= 0.0:
        return None
    return attn / rec["steps"] * 1e3
