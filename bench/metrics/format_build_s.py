"""``format_build_s``: the schedule choice and the eager forward that
builds the adjacency's feed formats, before the step is traced."""


def read(rec):
    """Seconds."""
    return rec["format_build_s"]
