"""``compile_s``: lowering and compiling the cell's step in set-up (the
persistent compile cache serves it after a checkout's first run)."""


def read(rec):
    """Seconds."""
    return rec["compile_s"]
