"""``spmm_window_share``: the share of the nnz tiles of the cell's eb
SpMM launches whose 'segment' reduce runs as MXU window products, by the
program's own count (``repro.kernels.ops.eb_window_tiles``, from the
window starts the kernel reads) over the lanes the step runs: the cell's
graph in the feed format of the schedule its selector picks."""


def read(rec):
    """Percent of the tiles, or None where the program keeps no such
    count or runs no eb launch."""
    try:
        from repro.kernels.ops import eb_window_tiles
    except ImportError:
        return None
    import jax.numpy as jnp

    from bench.traffic import gcn as traffic
    from repro.sparse import CSR, Schedule, matrix_stats

    cfg = rec["config"]
    graph = traffic.config_graph(cfg)
    adj = CSR(indptr=jnp.asarray(graph["indptr"]),
              indices=jnp.asarray(graph["indices"]),
              vals=jnp.asarray(graph["vals"]), shape=graph["shape"])
    sched = Schedule.auto(matrix_stats(adj), cfg["hidden"])
    if sched.kernel != "eb":
        return None
    lanes = adj.grouped(sched.nnz_tile, group_size=sched.group_size,
                        split_threshold=sched.split_threshold,
                        merge_threshold=sched.merge_threshold)
    return 100.0 * float(eb_window_tiles(lanes, sched.strategy).mean())
