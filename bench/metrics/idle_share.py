"""``idle_share``: the share of the traced window in which no operation
ran on the device."""


def read(rec):
    """Percent of the window, or None without a trace."""
    tr = rec["trace"]
    if not tr:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
