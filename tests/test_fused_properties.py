"""Property-based cross-backend harness for the fused/sharded SpMM path.

Hypothesis generates adversarial CSR structures — skewed, empty-row,
single-row, power-law degree — crossed with strategy and d, and asserts
the end-to-end oracles the deterministic suites spot-check:

  * the fused all-VPU plan (mxu_gain=0) == ref backend (allclose, f32
    accumulate),
  * sharded fused == unsharded fused BIT-identical (same per-row
    accumulation order; sharding must be a pure re-partitioning),
  * DMA-staged fused == resident fused BIT-identical across backends,
    strategies, skew families and chip counts (staging only moves
    operands, DESIGN.md §7.7 — it must not touch a bit),
  * plan/workspace balance invariants: efficiency in (0, 1], every
    output row packed exactly once, staged DMA windows in bounds.

Whole-module skip when hypothesis is absent (dev-only dependency), same
policy as test_plan.py.  Kernel-executing properties keep instances
small and example counts modest: every distinct (B, S, d_pad) shape is
a fresh interpret-mode compile.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (CSRMatrix, build_sharded_workspace, compile_spmm,
                        spmm)
from repro.core.jit_cache import JitCache
from repro.core.plan import (LANE, MAX_MERGE_WIDTH, MXU_TAG, STRATEGIES,
                             build_plan, build_workspace,
                             choose_merge_width)

N_DEV = len(jax.devices())
# the fused backend with every block-row tagged VPU, and as the
# structure tags it; sampled by key
FUSED = {"vpu": dict(backend="pallas_bcsr", mxu_gain=0.0),
         "pallas_bcsr": dict(backend="pallas_bcsr")}


def _csr_from_lengths(lengths, n, seed):
    """Deterministic CSR with given per-row nnz (capped at n)."""
    rng = np.random.default_rng(seed)
    lengths = np.minimum(np.asarray(lengths, np.int64), n)
    row_ptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    cols = np.concatenate(
        [np.sort(rng.choice(n, size=int(ln), replace=False))
         for ln in lengths] or [np.zeros(0, np.int64)]).astype(np.int32)
    vals = rng.standard_normal(int(row_ptr[-1])).astype(np.float32)
    return CSRMatrix((len(lengths), n), row_ptr, cols, vals)


@st.composite
def csr_cases(draw):
    """Adversarial structure families, all with concrete row lengths so
    shrinking stays meaningful."""
    n = draw(st.integers(1, 40))
    family = draw(st.sampled_from(
        ("skewed", "empty_rows", "single_row", "powerlaw")))
    seed = draw(st.integers(0, 10_000))
    if family == "single_row":
        lengths = [draw(st.integers(0, n))]
    elif family == "empty_rows":
        m = draw(st.integers(1, 24))
        lengths = [draw(st.integers(0, n)) if draw(st.booleans()) else 0
                   for _ in range(m)]
    elif family == "skewed":
        light = draw(st.integers(1, 20))
        heavy = draw(st.integers(1, 4))
        lengths = [1] * light + [n] * heavy
    else:  # powerlaw
        m = draw(st.integers(1, 24))
        rng = np.random.default_rng(seed)
        lengths = np.minimum(
            rng.zipf(1.8, size=m), n).astype(np.int64).tolist()
    return _csr_from_lengths(lengths, n, seed)


@settings(max_examples=12, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 24),
       strategy=st.sampled_from(STRATEGIES))
def test_fused_matches_ref(a, d, strategy):
    x = jnp.asarray(
        np.random.default_rng(d).standard_normal((a.n, d)), jnp.float32)
    y_ref = spmm(a, x, strategy=strategy, backend="ref", cache=JitCache())
    y = spmm(a, x, strategy=strategy, **FUSED["vpu"],
             interpret=True, cache=JitCache())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=12, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 24),
       strategy=st.sampled_from(STRATEGIES),
       chips=st.integers(1, 4))
def test_sharded_bit_matches_fused(a, d, strategy, chips):
    chips = min(chips, N_DEV)
    x = jnp.asarray(
        np.random.default_rng(d + 1).standard_normal((a.n, d)),
        jnp.float32)
    y0 = spmm(a, x, strategy=strategy, **FUSED["vpu"],
              interpret=True, cache=JitCache())
    y = spmm(a, x, strategy=strategy, **FUSED["vpu"],
             interpret=True, n_chips=chips, cache=JitCache())
    assert np.array_equal(np.asarray(y), np.asarray(y0))


@settings(max_examples=12, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 24),
       strategy=st.sampled_from(STRATEGIES))
def test_mixed_bcsr_matches_ref(a, d, strategy):
    """The mixed VPU/MXU dispatch (backend=pallas_bcsr) against the ref
    oracle on the same adversarial structure families — whatever the
    per-block-row tagging heuristic decided."""
    x = jnp.asarray(
        np.random.default_rng(d + 2).standard_normal((a.n, d)),
        jnp.float32)
    y_ref = spmm(a, x, strategy=strategy, backend="ref", cache=JitCache())
    y = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
             interpret=True, cache=JitCache())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=12, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 24),
       strategy=st.sampled_from(STRATEGIES),
       chips=st.integers(1, 4))
def test_sharded_mixed_bit_matches_fused(a, d, strategy, chips):
    chips = min(chips, N_DEV)
    x = jnp.asarray(
        np.random.default_rng(d + 3).standard_normal((a.n, d)),
        jnp.float32)
    y0 = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
              interpret=True, cache=JitCache())
    y = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
             interpret=True, n_chips=chips, cache=JitCache())
    assert np.array_equal(np.asarray(y), np.asarray(y0))


@settings(max_examples=8, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 24),
       strategy=st.sampled_from(STRATEGIES),
       backend=st.sampled_from(sorted(FUSED)))
def test_staged_bit_matches_resident(a, d, strategy, backend):
    """staging="dma" re-stages operands through double-buffered panel
    DMA but must reproduce the resident lowering BIT-for-bit on every
    adversarial structure family."""
    x = jnp.asarray(
        np.random.default_rng(d + 4).standard_normal((a.n, d)),
        jnp.float32)
    y_res = spmm(a, x, strategy=strategy, **FUSED[backend],
                 interpret=True, staging="resident", cache=JitCache())
    y_dma = spmm(a, x, strategy=strategy, **FUSED[backend],
                 interpret=True, staging="dma", cache=JitCache())
    assert np.array_equal(np.asarray(y_dma), np.asarray(y_res))


@settings(max_examples=8, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 16),
       strategy=st.sampled_from(STRATEGIES),
       backend=st.sampled_from(sorted(FUSED)),
       chips=st.integers(1, 4))
def test_staged_sharded_bit_matches_resident_sharded(a, d, strategy,
                                                     backend, chips):
    chips = min(chips, N_DEV)
    x = jnp.asarray(
        np.random.default_rng(d + 5).standard_normal((a.n, d)),
        jnp.float32)
    y_res = spmm(a, x, strategy=strategy, **FUSED[backend],
                 interpret=True, staging="resident", n_chips=chips,
                 cache=JitCache())
    y_dma = spmm(a, x, strategy=strategy, **FUSED[backend],
                 interpret=True, staging="dma", n_chips=chips,
                 cache=JitCache())
    assert np.array_equal(np.asarray(y_dma), np.asarray(y_res))


@settings(max_examples=8, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 16),
       strategy=st.sampled_from(STRATEGIES),
       backend=st.sampled_from(sorted(FUSED)),
       staging=st.sampled_from(("resident", "dma")),
       chips=st.integers(1, 4))
def test_xshard_bit_matches_replicated(a, d, strategy, backend, staging,
                                       chips):
    """x_sharding="rows" swaps X replication for the plan-time exact-
    panel exchange, but the kernel reads the same row VALUES in the
    same order — bit-identical on every adversarial structure family
    (skewed / empty-row / single-row / powerlaw), either staging."""
    chips = min(chips, N_DEV)
    x = jnp.asarray(
        np.random.default_rng(d + 6).standard_normal((a.n, d)),
        jnp.float32)
    y_rep = spmm(a, x, strategy=strategy, **FUSED[backend],
                 interpret=True, staging=staging, n_chips=chips,
                 x_sharding="replicated", cache=JitCache())
    y_row = spmm(a, x, strategy=strategy, **FUSED[backend],
                 interpret=True, staging=staging, n_chips=chips,
                 x_sharding="rows", cache=JitCache())
    assert np.array_equal(np.asarray(y_row), np.asarray(y_rep))


@settings(max_examples=40, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 32),
       strategy=st.sampled_from(STRATEGIES),
       chips=st.integers(1, 8))
def test_xshard_fetch_table_invariants(a, d, strategy, chips):
    """Host-only fetch-table invariants, any chip count: panel ids in
    range, padding sentinel is panel 0, owners' send rows stay inside
    their strip, and the remapped column stream addresses only the
    compact local workspace."""
    ws = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape, d,
                                 n_chips=chips, strategy=strategy,
                                 mxu_gain=0.0, x_sharding="rows")
    assert ws.x_panels == max(-(-a.n // ws.bk), 1)
    assert ws.x_own_panels * ws.n_chips >= ws.x_panels
    T = ws.x_local_panels
    assert T >= 1
    for c in range(ws.n_chips):
        assert ws.x_fetch[c, 0] == 0
        assert np.all((ws.x_fetch[c] >= 0)
                      & (ws.x_fetch[c] < ws.x_panels))
        assert np.all(ws.cols_flat[c] < T * ws.bk)
        assert np.all(ws.x_send[c] < ws.x_own_panels)
        assert np.all(ws.x_recv[c] < ws.n_chips * ws.x_send.shape[2])


@settings(max_examples=60, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 64),
       strategy=st.sampled_from(STRATEGIES))
def test_plan_efficiency_invariant(a, d, strategy):
    plan = build_plan(a.row_ptr, a.col_indices, a.shape, d,
                      strategy=strategy)
    assert 0 < plan.efficiency <= 1 or a.nnz == 0
    assert plan.padded_nnz >= a.nnz


@settings(max_examples=40, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 64),
       strategy=st.sampled_from(STRATEGIES),
       chips=st.integers(1, 12))
def test_sharded_workspace_invariants(a, d, strategy, chips):
    """Host-only packing invariants, any chip count (no mesh needed):
    row coverage is a bijection, efficiency stays in (0, 1], and the
    per-chip descriptor tables tile their real slots contiguously."""
    ws = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape, d,
                                 n_chips=chips, strategy=strategy,
                                 mxu_gain=0.0)
    assert ws.nnz == a.nnz
    if a.nnz:
        assert 0 < ws.efficiency <= 1
    assert len(set(ws.inv_perm.tolist())) == a.m
    if a.m:
        assert np.all(ws.inv_perm < ws.n_chips * ws.ws_rows)
    bm = ws.row_block
    for c in range(ws.n_chips):
        L = ws.blk_L[c]
        real = L > 0
        ends = ws.blk_off[c].astype(np.int64) + bm * L.astype(np.int64)
        # real blocks tile [0, slots) in order; pads carry zero work
        n_real = int(real.sum())
        if n_real:
            np.testing.assert_array_equal(ws.blk_off[c][1:n_real],
                                          ends[:n_real - 1])
            assert ws.blk_off[c][0] == 0
        # gather stays inside the global concat(vals,[0]) buffer
        assert np.all(ws.gather_flat[c] <= a.nnz)
    # staged-DMA windows (DESIGN.md §7.7) never read past the streams;
    # windows are PER CHIP since the hot-shard fix (each chip's staged
    # kernel uses its own chip_span, not the cross-chip max)
    assert int(np.asarray(ws.chip_span).max(initial=0)) == ws.max_span
    assert np.all(ws.blk_off + np.asarray(ws.chip_span)[:, None]
                  <= ws.gather_flat.shape[1])
    assert np.all(ws.blk_coff + np.asarray(ws.chip_cspan)[:, None]
                  <= ws.cols_flat.shape[1])


# ---------------------------------------------------------------------------
# CGCM (coarse-grain row merging, DESIGN.md §7.9): a merged plan bakes
# W descriptors into one grid step but every row still reduces its own
# lanes in-register, so the output must be BIT-identical to the
# unmerged plan — end to end, both backends, both stagings, any chip
# count, forward and gradient.
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 16),
       strategy=st.sampled_from(STRATEGIES),
       backend=st.sampled_from(sorted(FUSED)),
       staging=st.sampled_from(("resident", "dma")),
       chips=st.integers(1, 4))
def test_merged_bit_matches_unmerged(a, d, strategy, backend, staging,
                                     chips):
    chips = min(chips, N_DEV)
    x = jnp.asarray(
        np.random.default_rng(d + 7).standard_normal((a.n, d)),
        jnp.float32)
    y0 = spmm(a, x, strategy=strategy, **FUSED[backend], interpret=True,
              staging=staging, n_chips=chips, merge_threshold=0,
              cache=JitCache())
    y1 = spmm(a, x, strategy=strategy, **FUSED[backend], interpret=True,
              staging=staging, n_chips=chips, merge_threshold=16,
              cache=JitCache())
    assert np.array_equal(np.asarray(y1), np.asarray(y0))


@settings(max_examples=6, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 8),
       strategy=st.sampled_from(STRATEGIES),
       backend=st.sampled_from(sorted(FUSED)))
def test_merged_gradient_bit_matches_unmerged(a, d, strategy, backend):
    """The custom-VJP backward runs through the same fused dispatch, so
    merging must not perturb a gradient bit either."""
    x = jnp.asarray(
        np.random.default_rng(d + 8).standard_normal((a.n, d)),
        jnp.float32)
    vals = jnp.asarray(a.vals)
    grads = []
    for threshold in (0, 16):
        c = compile_spmm(a, d, strategy=strategy, **FUSED[backend],
                         interpret=True, merge_threshold=threshold,
                         cache=JitCache())

        def f(v, xx, c=c):
            return jnp.sum(c(v, xx) ** 2)

        grads.append(jax.grad(f, argnums=(0, 1))(vals, x))
    assert np.array_equal(np.asarray(grads[0][0]), np.asarray(grads[1][0]))
    assert np.array_equal(np.asarray(grads[0][1]), np.asarray(grads[1][1]))


@pytest.mark.parametrize("backend", [*FUSED, "ref"])
def test_gradient_of_a_matrix_without_nonzeros(backend):
    """The shrunk example hypothesis found for the property above: a
    (1, 1) matrix with no nonzero.  The chunked SDDMM must not divide
    its zero nonzeros into chunks of size zero."""
    a = CSRMatrix((1, 1), np.zeros(2, np.int64), np.zeros(0, np.int32),
                  np.zeros(0, np.float32))
    c = compile_spmm(a, 1, strategy="row_split",
                     **FUSED.get(backend, dict(backend=backend)),
                     interpret=True, cache=JitCache())
    dvals, dx = jax.grad(lambda v, xx: jnp.sum(c(v, xx) ** 2),
                         argnums=(0, 1))(jnp.zeros((0,), jnp.float32),
                                         jnp.ones((1, 1), jnp.float32))
    assert dvals.shape == (0,)
    assert np.array_equal(np.asarray(dx), np.zeros((1, 1), np.float32))


@settings(max_examples=40, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 32),
       strategy=st.sampled_from(STRATEGIES),
       mixed=st.booleans(),
       threshold=st.sampled_from((0, 4, 16, 64)))
def test_merged_workspace_invariants(a, d, strategy, mixed, threshold):
    """Host-only merged-trip packing invariants: the width is the merge
    stage's power-of-two pick, the descriptor table pads to a multiple
    of W with inert zero-trip blocks, trips never mix VPU and MXU
    members, per-trip DMA windows are exactly the sum of the member
    extents and stay in bounds, and W == 1 is byte-identical to the
    unmerged packer."""
    ws = build_workspace(a.row_ptr, a.col_indices, a.shape, d,
                         strategy=strategy,
                         mxu_gain=4.0 if mixed else 0.0,
                         merge_threshold=threshold)
    W = ws.merge_width
    assert 1 <= W <= MAX_MERGE_WIDTH and (W & (W - 1)) == 0
    assert W == choose_merge_width(a.row_ptr, row_block=ws.row_block,
                                   merge_threshold=threshold)
    assert ws.num_blocks % W == 0
    assert ws.num_trips * W == ws.num_blocks
    assert ws.blk_span.shape[0] == ws.num_trips
    assert ws.blk_cspan.shape[0] == ws.num_trips
    # per-trip windows == sum of member extents (streams contiguous)
    bm = ws.row_block
    L = ws.blk_L.astype(np.int64)
    per_span = np.where(ws.blk_tag == MXU_TAG, L * bm * LANE, bm * L)
    per_cspan = np.where(ws.blk_tag == MXU_TAG, L, bm * L)
    np.testing.assert_array_equal(ws.blk_span,
                                  per_span.reshape(-1, W).sum(axis=1))
    np.testing.assert_array_equal(ws.blk_cspan,
                                  per_cspan.reshape(-1, W).sum(axis=1))
    # fixed-size staged copies fit for every merged trip
    assert np.all(ws.blk_off[::W].astype(np.int64) + ws.max_span
                  <= ws.gather_flat.shape[0])
    assert np.all(ws.blk_coff[::W].astype(np.int64) + ws.max_cspan
                  <= ws.cols_flat.shape[0])
    trip_tags = ws.blk_tag.reshape(-1, W)
    assert np.all(trip_tags == trip_tags[:, :1])
    # the unmerged build's descriptors, in order: CGCM only inserts
    # inert zero-trip pads (to fill a trip, or before a tag change)
    ws0 = build_workspace(a.row_ptr, a.col_indices, a.shape, d,
                          strategy=strategy,
                          mxu_gain=4.0 if mixed else 0.0,
                          merge_threshold=0)
    real = ws.blk_L > 0
    assert int(real.sum()) == ws0.num_blocks
    np.testing.assert_array_equal(ws.blk_off[real], ws0.blk_off)
    np.testing.assert_array_equal(ws.blk_L[real], ws0.blk_L)
    np.testing.assert_array_equal(ws.blk_tag[real], ws0.blk_tag)
    np.testing.assert_array_equal(ws.blk_coff[real], ws0.blk_coff)
    real_slots = ws0.gather_flat.shape[0] - ws0.max_span
    real_cols = ws0.cols_flat.shape[0] - ws0.max_cspan
    np.testing.assert_array_equal(ws.gather_flat[:real_slots],
                                  ws0.gather_flat[:real_slots])
    np.testing.assert_array_equal(ws.cols_flat[:real_cols],
                                  ws0.cols_flat[:real_cols])
    if W == 1:
        # byte-identical to the legacy packer — nothing moved at all
        for f in ("blk_off", "blk_L", "blk_tag", "blk_coff", "blk_span",
                  "blk_cspan", "gather_flat", "cols_flat", "inv_perm"):
            np.testing.assert_array_equal(getattr(ws, f), getattr(ws0, f))
        assert (ws.max_span, ws.max_cspan) == (ws0.max_span,
                                               ws0.max_cspan)


@settings(max_examples=30, deadline=None)
@given(a=csr_cases(), d=st.integers(1, 32),
       strategy=st.sampled_from(STRATEGIES),
       chips=st.integers(1, 8),
       threshold=st.sampled_from((0, 16)))
def test_sharded_merged_workspace_invariants(a, d, strategy, chips,
                                             threshold):
    """The sharded pipeline merges BEFORE partitioning: one global width
    for every chip, chip bounds cut at merged-trip boundaries, per-chip
    staged windows sized to merged trips and still in bounds."""
    ws = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape, d,
                                 n_chips=chips, strategy=strategy,
                                 mxu_gain=0.0, merge_threshold=threshold)
    W = ws.merge_width
    assert 1 <= W <= MAX_MERGE_WIDTH and (W & (W - 1)) == 0
    assert W == choose_merge_width(a.row_ptr, row_block=ws.row_block,
                                   merge_threshold=threshold)
    B = ws.blk_off.shape[1]
    assert B % W == 0
    assert ws.num_trips * W == B
    # every chip packed with the global width
    assert all(getattr(p, "row_block", ws.row_block) == ws.row_block
               for p in ws.shard_plans)
    assert int(np.asarray(ws.chip_span).max(initial=0)) == ws.max_span
    assert np.all(ws.blk_off[:, ::W] + np.asarray(ws.chip_span)[:, None]
                  <= ws.gather_flat.shape[1])
    assert np.all(ws.blk_coff[:, ::W] + np.asarray(ws.chip_cspan)[:, None]
                  <= ws.cols_flat.shape[1])
