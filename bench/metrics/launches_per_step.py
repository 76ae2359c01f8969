"""``launches_per_step``: Pallas kernel launches (``tpu_custom_call``) in
the compiled step's HLO."""

from bench import trace


def read(rec):
    """The count."""
    return len(trace.pallas_launches(rec["hlo"]))
