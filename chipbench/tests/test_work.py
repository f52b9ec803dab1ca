"""The least work of a step, counted from the problem's own shapes."""
import numpy as np
import pytest

from chipbench import run as R

spmm = R.load_module("work", "spmm")
attention = R.load_module("work", "attention")


def test_spmm_work_of_a_tiny_matrix():
    # 3 x 4, nonzeros per row 2, 1, 2; d = 2
    structure = (np.array([0, 2, 3, 5]), np.array([0, 3, 1, 0, 2]), (3, 4))
    fwd = spmm.count(structure, {"width": 2}, {"grad": False})
    # 2 nnz d; 8 B per nonzero + row_ptr 4 * 4 + X 4 * 4 * 2 + Y 4 * 3 * 2
    assert fwd == {"flops": 20, "bytes": 40 + 16 + 32 + 24}
    train = spmm.count(structure, {"width": 2}, {"grad": True})
    # + dY read (24), dX write (32), dvals write (20); three products
    assert train == {"flops": 60, "bytes": 112 + 24 + 32 + 20}


def test_attention_work_of_a_tiny_mask():
    # S = 4, 10 entries, 2 heads of 8
    structure = (np.array([0, 4, 6, 8, 10]), np.zeros(10, np.int32), (4, 4))
    w = attention.count(structure, {"num_attention_heads": 2,
                                    "head_dim": 8}, {})
    # 4 nnz dh per head; Q, K, V, O per head + mask cols/weights + row_ptr
    assert w == {"flops": 2 * 4 * 10 * 8,
                 "bytes": 2 * 4 * (4 * 8 * 4) + 10 * 8 + 5 * 4}


def test_pokec_forward_least_time_is_memory_bound():
    nnz, n, d = 30_622_564, 1_632_803, 128
    structure = (None, np.broadcast_to(np.int32(0), (nnz,)), (n, n))
    w = spmm.count(structure, {"width": d}, {"grad": False})
    peaks = R.peaks_for("TPU v5 lite")
    t_mem = w["bytes"] / peaks["hbm_bytes_per_s"]
    assert t_mem > w["flops"] / peaks["flops_per_s"]
    assert t_mem == pytest.approx(2.35e-3, rel=0.01)


def test_unknown_chip_has_no_peaks():
    with pytest.raises(SystemExit):
        R.peaks_for("TPU v99")
