"""Deterministic coverage for ``partition_rows_for_chips`` and the
sharded-workspace packing built on it — runs even without hypothesis
(the property-based twin lives in test_plan.py /
test_fused_properties.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_sharded_workspace,
                        partition_rows_for_chips, random_csr, spmm)
from repro.core.jit_cache import JitCache
from repro.core.plan import STRATEGIES


def _row_ptr(lengths):
    return np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)


CASES = {
    "empty": _row_ptr([]),
    "single_row": _row_ptr([5]),
    "single_empty_row": _row_ptr([0]),
    "uniform": _row_ptr([3] * 64),
    "skewed_head": _row_ptr([1000] + [1] * 63),
    "skewed_tail": _row_ptr([1] * 63 + [1000]),
    "all_empty": _row_ptr([0] * 32),
}


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("name", sorted(CASES))
@pytest.mark.parametrize("chips", [1, 2, 7, 64])
def test_bounds_monotone_and_cover(strategy, name, chips):
    row_ptr = CASES[name]
    m = len(row_ptr) - 1
    bounds = partition_rows_for_chips(row_ptr, chips, strategy)
    assert bounds.shape == (chips + 1,)
    assert bounds[0] == 0
    assert bounds[-1] == m
    assert np.all(np.diff(bounds) >= 0), (strategy, name, bounds)
    assert np.all((bounds >= 0) & (bounds <= m))


def test_unknown_strategy_raises():
    with pytest.raises(ValueError):
        partition_rows_for_chips(_row_ptr([1, 2]), 2, "bogus")


def test_nnz_split_balances_skew():
    # the giant head row must get (roughly) its own chip
    row_ptr = CASES["skewed_head"]
    bounds = partition_rows_for_chips(row_ptr, 4, "nnz_split")
    assert bounds[1] <= 2          # chip 0 ends right after the hot row


# -- shard-count edge cases (workspace packing is host-only: no mesh) ------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_more_chips_than_rows(strategy):
    """n_chips > n_rows: the surplus chips get empty row ranges and pad
    descriptor tables (blk_L == 0), and every real row is still packed
    exactly once."""
    a = random_csr(3, 10, density=0.5, family="uniform", seed=1)
    ws = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape, 8,
                                 n_chips=16, strategy=strategy)
    assert ws.n_chips == 16
    assert ws.bounds[0] == 0 and ws.bounds[-1] == a.m
    rows_per_chip = np.diff(ws.bounds)
    assert rows_per_chip.sum() == a.m
    assert (rows_per_chip == 0).sum() >= 16 - a.m
    # global inv_perm is a bijection onto distinct workspace rows
    assert len(set(ws.inv_perm.tolist())) == a.m
    assert np.all(ws.inv_perm < 16 * max(ws.ws_rows, 1))
    # every chip's real work sums to the matrix nnz
    assert ws.nnz == a.nnz
    assert 0 < ws.efficiency <= 1 or a.nnz == 0


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_all_nnz_in_one_row(strategy):
    """One hot row owning every nonzero: nnz_split must isolate it while
    the empty rows still come out zero."""
    lengths = [0] * 11 + [37] + [0] * 12
    row_ptr = _row_ptr(lengths)
    cols = np.arange(37, dtype=np.int32) % 40
    ws = build_sharded_workspace(row_ptr, cols, (24, 40), 8,
                                 n_chips=4, strategy=strategy)
    assert ws.nnz == 37
    assert 0 < ws.efficiency <= 1
    if strategy == "nnz_split":
        # the hot row's chip carries (essentially) all the padded work
        chip = int(np.searchsorted(ws.bounds[1:], 11, side="right"))
        per_chip = ws.row_block * ws.blk_L.astype(np.int64).sum(axis=1)
        assert per_chip[chip] >= 37


# -- align=bm degenerate cases (the block-row clamp bugfix) ----------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_align_more_chips_than_block_rows(strategy):
    """n_chips > block-rows with align=bm: rounding used to leave empty
    chips BEFORE populated ones ([0, 0, 8, 8, 8] on a single block-row
    — chip 0 empty, chip 1 everything).  Populated chips must come
    first, one block-row minimum each, surplus chips empty at the end."""
    for n_rows, chips in ((8, 4), (16, 4), (24, 7)):
        row_ptr = _row_ptr([3] * n_rows)
        bounds = partition_rows_for_chips(row_ptr, chips, strategy,
                                          align=8)
        sizes = np.diff(bounds)
        assert bounds[0] == 0 and bounds[-1] == n_rows
        assert np.all(sizes >= 0)
        # interior bounds stay block-row aligned
        assert np.all(bounds[1:-1] % 8 == 0), (strategy, bounds)
        # no empty chip before a populated one
        populated = np.nonzero(sizes)[0]
        assert populated.size == min(chips, n_rows // 8), (strategy,
                                                           bounds)
        assert np.array_equal(populated,
                              np.arange(populated.size)), (strategy,
                                                           bounds)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_align_single_block_row(strategy):
    """One (ragged) block-row, several chips: chip 0 owns everything."""
    row_ptr = _row_ptr([2] * 5)      # m=5 < bm=8: one ragged block-row
    bounds = partition_rows_for_chips(row_ptr, 3, strategy, align=8)
    assert np.array_equal(bounds, [0, 5, 5, 5]), (strategy, bounds)


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("align", [1, 8])
def test_align_empty_matrix(strategy, align):
    bounds = partition_rows_for_chips(_row_ptr([]), 4, strategy,
                                      align=align)
    assert np.array_equal(bounds, [0, 0, 0, 0, 0]), (strategy, bounds)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_align_no_middle_empty_chip_on_skew(strategy):
    """A hot head block-row must not strand later chips empty while
    block-rows remain: every chip before the end gets >= 1 block-row."""
    row_ptr = _row_ptr([200] * 8 + [1] * 24)     # hot first block-row
    bounds = partition_rows_for_chips(row_ptr, 4, strategy, align=8)
    sizes = np.diff(bounds)
    populated = np.nonzero(sizes)[0]
    assert np.array_equal(populated, np.arange(populated.size))
    if strategy != "row_split":
        assert sizes[0] >= 8      # the hot block-row stays on chip 0


def test_align_sharded_workspace_packs_degenerate_shards():
    """End-to-end: the mixed (align=bm) sharded workspace on more chips
    than block-rows still packs every row exactly once and matches the
    unsharded fused dispatch bit-for-bit."""
    a = random_csr(10, 32, density=0.3, family="uniform", seed=7)
    ws = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape, 8,
                                 n_chips=6)
    assert len(set(ws.inv_perm.tolist())) == a.m
    assert ws.nnz == a.nnz
    x = jnp.asarray(
        np.random.default_rng(8).standard_normal((a.n, 8)), jnp.float32)
    y0 = spmm(a, x, backend="pallas_bcsr", interpret=True,
              cache=JitCache())
    if len(jax.devices()) >= 2:
        y = spmm(a, x, backend="pallas_bcsr", interpret=True, n_chips=2,
                 cache=JitCache())
        assert np.array_equal(np.asarray(y), np.asarray(y0))


def test_n_chips_1_bit_matches_unsharded_fused():
    """The sharded machinery with a single chip must be a bit-exact
    no-op relative to the plain fused path (same sub-plan, same kernel,
    same accumulation order)."""
    a = random_csr(64, 48, density=0.1, family="powerlaw", seed=5)
    x = jnp.asarray(
        np.random.default_rng(6).standard_normal((a.n, 20)), jnp.float32)
    for strategy in STRATEGIES:
        y0 = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
                  mxu_gain=0.0, interpret=True, cache=JitCache())
        y1 = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
                  mxu_gain=0.0, interpret=True, n_chips=1,
                  cache=JitCache())
        assert np.array_equal(np.asarray(y0), np.asarray(y1)), strategy
