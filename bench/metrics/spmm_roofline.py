"""``spmm_roofline``: the least time of the SpMMs that the step's kernel
launches run, counted from logical shapes (``bench.counts``), over the
launches' measured time.  The launches are the compiled step's (by its
HLO), each matched to the logical SpMM it computes."""

from bench import counts, trace


def read(rec):
    """Percent of the roofline, or None where no SpMM kernel ran or the
    launches are more than the step has SpMMs."""
    tr = rec["trace"]
    launches = [lc for lc in trace.pallas_launches(rec["hlo"]) if lc["kernel"]]
    spmm = trace.spmm_seconds(tr, launches) if tr else 0.0
    if spmm <= 0.0:
        return None
    logical = counts.gcn_spmm_launches(rec["config"],
                                       [lc["backward"] for lc in launches])
    if logical is None:
        return None
    least = counts.spmm_least_time_s(logical, rec["peak"])
    return least / (spmm / rec["steps"]) * 100.0
