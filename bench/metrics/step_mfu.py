"""``step_mfu``: the step's model operations per second over the chip's
bf16 peak, in the traced window."""


def read(rec):
    """Percent of peak, or None without a trace."""
    tr = rec["trace"]
    if not tr:
        return None
    rate = rec["steps"] / tr["window_s"]
    return rec["model_flops"] * rate / rec["peak"]["bf16_flops_per_s"] * 100.0
