"""``stream_bytes_ratio``: the HBM bytes that the schedule of a deep GCN
step's windowed SpMM launches moves (``bench.counts_gcn_deep.
scheduled_bytes``, over the partition the run used: the program's
``BlockedCOO`` of the run's adjacency under its schedule, for each
forward layer's width) over the compulsory bytes of the same SpMMs
(``bench.counts.spmm_bytes``).  Above 1 by the B rows fetched again for
each row window, the padded and re-read lanes."""

from bench import counts, counts_gcn_deep


def read(rec):
    """The ratio, or None where the run kept no adjacency and schedule,
    or where a forward launch of the step runs resident."""
    adj, sched = rec.get("adjacency"), rec.get("schedule")
    if adj is None or sched is None or "layers" not in rec["config"]:
        return None
    try:
        from repro.kernels.common import segment_window
        from repro.kernels.ops import eb_stream_layout
    except ImportError:
        return None
    moved = needed = 0
    for s in counts_gcn_deep.forward_spmms(rec["config"]):
        layout = eb_stream_layout(adj, s["n"], sched)
        if layout is None:
            return None
        blk, col_tile = layout
        moved += counts_gcn_deep.scheduled_bytes(
            blk.tile_rows, blk.tile_cols, nnz_padded=blk.nnz_padded,
            n_rows=adj.shape[0], n_cols=adj.shape[1], windows=blk.windows,
            n=s["n"], col_tile=col_tile, bias=s["bias"],
            window_reduce=segment_window(sched.strategy, blk.windows[0],
                                         sched.nnz_tile) is not None)
        needed += counts.spmm_bytes(s["n_rows"], s["n_cols"], s["nnz"], s["n"],
                                    bias=s["bias"])
    return moved / needed
