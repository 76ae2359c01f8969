"""Counts of operations and bytes from logical shapes, and the peaks."""
import pytest

from bench import counts
from bench.run import cell_spec

PUBMED = dict(n_nodes=19717, n_entries=108393, n_features=500, hidden=16,
              n_classes=3)


def test_pubmed_spmm_counts_by_hand():
    """Pubmed spmm counts by hand."""
    # launch 1: A (19,717 x 19,717, 108,393 entries) @ B (19,717 x 16) + b
    assert counts.spmm_flops(108393, 16) == 3_468_576
    assert counts.spmm_bytes(19717, 19717, 108393, 16, bias=True) == (
        108393 * 8 + 19718 * 4 + 19717 * 16 * 4 * 2 + 16 * 4) == 3_469_856
    # launch 2: the same A @ (19,717 x 3) + b
    assert counts.spmm_flops(108393, 3) == 650_358
    assert counts.spmm_bytes(19717, 19717, 108393, 3, bias=True) == 1_419_236
    launches = counts.gcn_spmm_launches(PUBMED)
    assert [lc["n"] for lc in launches] == [16, 3]
    peak = counts.peaks("TPU v5 lite")
    # bytes bound: 4,889,092 bytes at 819 GB/s
    assert counts.spmm_least_time_s(launches, peak) == pytest.approx(
        4_889_092 / 819e9, rel=1e-12)
    assert counts.least_time_s(3_468_576, 3_469_856, peak)[1] == "bytes"


def test_pubmed_step_flops_by_hand():
    """Pubmed step flops by hand."""
    x_w0 = 2 * 19717 * 500 * 16  # forward X W0, and again for dW0
    h_w1 = 2 * 19717 * 16 * 3    # forward H W1, dW1 and dH
    spmm = 2 * 108393 * (16 + 3)  # forward SpMMs, and again for A^T
    assert counts.gcn_train_flops(PUBMED) == 2 * x_w0 + 3 * h_w1 + 2 * spmm
    assert counts.gcn_train_flops(PUBMED) == 644_860_364


def test_backward_launches_count_the_transposed_spmms():
    """Backward launches count the transposed spmms."""
    fwd_bwd = counts.gcn_spmm_launches(PUBMED, [False, False, True, True])
    assert [(lc["n"], lc["bias"]) for lc in fwd_bwd] == [
        (16, True), (3, True), (3, False), (16, False)]
    assert counts.gcn_spmm_launches(PUBMED, [False] * 3) is None
    assert counts.gcn_spmm_launches(PUBMED, []) == []


def test_two_schedules_of_one_matrix_count_alike():
    """Two schedules of one matrix count alike."""
    from repro.sparse.formats import CSR

    from bench.traffic.gcn import gcn_graph

    g = gcn_graph(500, 1500, seed=3)
    csr = CSR(indptr=g["indptr"], indices=g["indices"], vals=g["vals"],
              shape=g["shape"])
    a = csr.grouped(128)
    b = csr.grouped(512, group_size=8, split_threshold=16, merge_threshold=2)
    assert a.nnz_padded != b.nnz_padded  # the schedules stream different lanes
    for n in (3, 16):
        want = (counts.spmm_flops(csr.nnz, n),
                counts.spmm_bytes(*csr.shape, csr.nnz, n, bias=True))
        for fmt in (a, b):
            assert (counts.spmm_flops(fmt.nnz, n),
                    counts.spmm_bytes(*fmt.shape, fmt.nnz, n, bias=True)) == want


def test_unknown_device_kind_raises():
    """Unknown device kind raises."""
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("cell", ["gcn-pubmed.train", "gcn-cora.train"])
def test_roofline_share_of_a_cell_stays_under_peak(cell):
    """Roofline share of a cell stays under peak."""
    cfg = cell_spec(cell)["config"]
    peak = counts.peaks("TPU v5 lite")
    least = counts.spmm_least_time_s(counts.gcn_spmm_launches(cfg), peak)
    assert 0 < least < 1e-5  # a few microseconds of compulsory traffic
