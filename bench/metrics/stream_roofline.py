"""``stream_roofline``: the least time of the forward SpMMs of a deep GCN
training step (each layer's ``A (H W) + b``, counted from logical shapes
by ``bench.counts_gcn_deep``, whatever layout implements them) over the
measured time of the step's SpMM kernel launches."""

from bench import counts_gcn_deep, trace


def read(rec):
    """Percent of the roofline, or None without a trace, for a
    configuration that states no layer count, or where no SpMM kernel
    ran."""
    tr, cfg = rec["trace"], rec["config"]
    if not tr or "layers" not in cfg:
        return None
    spmm = trace.spmm_seconds(tr, trace.pallas_launches(rec["hlo"]))
    if spmm <= 0.0:
        return None
    least = counts_gcn_deep.forward_spmm_least_time_s(cfg, rec["peak"])
    return least / (spmm / rec["steps"]) * 100.0
