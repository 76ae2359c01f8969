"""``attn_roofline``: the least time of a GAT training step's attention,
each layer's forward and backward counted from logical shapes
(``bench.counts_gat``), over the measured time of the step's fused
attention launches."""

from bench import counts_gat


def read(rec):
    """Percent of the roofline, or None without a trace, where no
    attention kernel ran, or where the step's attention launches are not
    one forward and one backward a layer."""
    tr = rec["trace"]
    if not tr:
        return None
    launches = counts_gat.attn_launches(rec["hlo"])
    kinds = sorted(lc["kernel"] for lc in launches)
    layers = len(counts_gat.attn_layers(rec["config"])) if "heads" in rec["config"] else 0
    if not layers or kinds != sorted(counts_gat.ATTN_KERNELS * layers):
        return None
    attn = sum(tr["ops"].get(lc["name"], 0.0) for lc in launches)
    if attn <= 0.0:
        return None
    least = counts_gat.attn_least_time_s(rec["config"], rec["peak"])
    return least / (attn / rec["steps"]) * 100.0
