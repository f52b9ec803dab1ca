"""The structure generators: exact counts, no duplicate edges, and the
same structure from the same structure seed."""
import json

import numpy as np

from chipbench import run as R

graph = R.load_module("gen", "power_law_graph")
mask = R.load_module("gen", "longformer_mask")

SMALL = dict(nodes=20_000, edges=250_000, out_degree_shape=1.5,
             out_degree_cap=2_000, in_rank_exponent=0.5)


def _rows(row_ptr):
    return np.repeat(np.arange(row_ptr.size - 1), np.diff(row_ptr))


def test_graph_has_exact_counts_and_no_repeated_edges():
    row_ptr, cols, shape = graph.generate(SMALL, 7)
    n = SMALL["nodes"]
    assert shape == (n, n)
    assert row_ptr[0] == 0 and row_ptr[-1] == SMALL["edges"]
    assert cols.shape == (SMALL["edges"],) and cols.dtype == np.int32
    rows = _rows(row_ptr)
    keys = rows * n + cols
    assert np.all(np.diff(keys) > 0)           # sorted, no duplicates
    assert not np.any(rows == cols)            # no self-loops
    assert np.diff(row_ptr).max() <= SMALL["out_degree_cap"]
    assert cols.min() >= 0 and cols.max() < n


def test_graph_is_fixed_by_its_structure_seed():
    a, b, c = (graph.generate(SMALL, s) for s in (7, 7, 8))
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[1], c[1])


def test_graph_degrees_follow_power_laws():
    row_ptr, cols, _ = graph.generate(SMALL, 7)
    out_deg = np.diff(row_ptr)
    in_deg = np.bincount(cols, minlength=SMALL["nodes"])
    mean = SMALL["edges"] / SMALL["nodes"]
    # heavy tails: the hottest row and column are far above the mean
    assert out_deg.max() > 20 * mean and in_deg.max() > 20 * mean


def test_pokec_config_keeps_the_published_counts():
    cfg = json.loads((R.BENCH_DIR / "configs" /
                      "soc-pokec-d128.json").read_text())
    s = cfg["structure"]
    assert (s["generator"], s["nodes"], s["edges"]) == (
        "power_law_graph", 1_632_803, 30_622_564)


def _brute_mask(S, window, g):
    w = window // 2
    return [[j for j in range(S)
             if abs(i - j) <= w or i < g or j < g] for i in range(S)]


def test_mask_matches_the_longformer_pattern():
    S, window, g = 96, 16, 5
    row_ptr, cols, shape = mask.generate(
        dict(seq_len=S, attention_window=window, global_tokens=g))
    assert shape == (S, S)
    want = _brute_mask(S, window, g)
    got = [cols[row_ptr[i]:row_ptr[i + 1]].tolist() for i in range(S)]
    assert got == want


def test_longformer_large_mask_nonzeros():
    cfg = json.loads((R.BENCH_DIR / "configs" /
                      "longformer-large-4096.json").read_text())
    row_ptr, cols, shape = mask.generate(cfg["structure"])
    assert shape == (4096, 4096)
    assert row_ptr[-1] == cols.size == 2_522_816
    assert np.diff(row_ptr)[:64].tolist() == [4096] * 64   # global rows
    assert np.diff(row_ptr)[1000] == 64 + 513              # window + globals
