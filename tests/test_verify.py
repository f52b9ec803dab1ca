"""Mutation tests for the static plan verifier (DESIGN.md §15).

Every invariant class gets one targeted corruption — built by taking a
REAL pipeline artifact and flipping exactly the field the invariant
guards with ``dataclasses.replace`` — and the test asserts the verifier
reports the exact violation kind.  Clean round-trips then pin the
other direction: everything the pipeline actually emits, across
strategy x tagging x staging x chips, verifies with zero
error-severity findings (so turning ``validate="full"`` on under the
whole suite cannot regress anything).
"""
import dataclasses

import numpy as np
import pytest

from repro.analysis.verify import (VALIDATE_MODES, PlanVerificationError,
                                   check_workspace, resolve_validate,
                                   verify_attention_contract,
                                   verify_workspace)
from repro.core.csr import CSRMatrix, random_csr
from repro.core.plan import (MXU_TAG, SPARSE_ATTN_MIXED_EINSUM,
                             build_batched_workspace, build_einsum_workspace,
                             build_fused_workspace, build_mixed_plan,
                             build_sharded_workspace, build_workspace)


def _kinds(violations):
    return {v.kind for v in violations if v.severity == "error"}


def _solo(m=64, n=64, *, density=0.2, mixed=False, merge_threshold=0,
          seed=0, family="uniform", d=16):
    a = random_csr(m, n, density=density, seed=seed, family=family)
    ws = build_workspace(a.row_ptr, a.col_indices, a.shape, d,
                         mxu_gain=4.0 if mixed else 0.0,
                         merge_threshold=merge_threshold)
    return a, ws


def _sharded(m=96, n=96, *, n_chips=2, mxu_gain=0.0,
             x_sharding="replicated", density=0.15, seed=1, d=16,
             merge_threshold=0):
    a = random_csr(m, n, density=density, seed=seed)
    sw = build_sharded_workspace(
        a.row_ptr, a.col_indices, a.shape, d, n_chips=n_chips,
        mxu_gain=mxu_gain, x_sharding=x_sharding,
        merge_threshold=merge_threshold)
    return a, sw


def _batched(R=3, m=24, n=32, *, d=16, seed=2):
    mats = [random_csr(m, n, density=0.2, seed=seed + r)
            for r in range(R)]
    structures = [(a.row_ptr, a.col_indices, a.shape) for a in mats]
    return mats, build_batched_workspace(structures, d)


def _mixed_csr_for_verify(m=48, n=64, seed=0):
    """Two dense block-rows (MXU) over a ragged sparse tail (VPU)."""
    rng = np.random.default_rng(seed)
    dense = np.zeros((m, n), np.float32)
    for i in range(16):
        dense[i, (i // 8) * 16:(i // 8) * 16 + 16] = 1.0
    for i in range(16, m):
        dense[i, rng.choice(n, size=2, replace=False)] = 1.0
    return CSRMatrix.from_dense(dense)


# -- mutation tests: one corruption per invariant class ----------------------


def test_blk_off_monotone_decreasing_offsets():
    a, ws = _solo()
    real = np.flatnonzero(ws.blk_L > 0)
    assert real.size >= 2, "need two real blocks to break monotonicity"
    off = ws.blk_off.copy()
    # move the SECOND real offset below the first: decreasing stream
    off[real[1]] = off[real[0]] - 1
    bad = dataclasses.replace(ws, blk_off=off)
    assert "blk_off_monotone" in _kinds(
        verify_workspace(bad, n_cols=a.n))


def test_blk_bounds_shifted_offsets():
    a, ws = _solo()
    # a uniform +shift keeps monotonicity but pushes the last real
    # extent past the real region's end
    bad = dataclasses.replace(
        ws, blk_off=ws.blk_off + np.int32(ws.gather_flat.shape[0]))
    assert "blk_bounds" in _kinds(verify_workspace(bad, n_cols=a.n))


def test_trip_span_disagrees_with_members():
    a, ws = _solo()
    assert ws.blk_span is not None
    span = ws.blk_span.copy()
    span[0] += 1
    bad = dataclasses.replace(ws, blk_span=span)
    assert "trip_span" in _kinds(verify_workspace(bad, n_cols=a.n))


def test_pad_block_live_zero_trip_block_still_read():
    a, ws = _solo()
    # zero out the trip count of the block that output row 0 reads:
    # its workspace rows are never written, yet inv_perm gathers them
    blk = int(ws.inv_perm[0]) // ws.row_block
    L = ws.blk_L.copy()
    L[blk] = 0
    bad = dataclasses.replace(ws, blk_L=L)
    assert "pad_block_live" in _kinds(verify_workspace(bad, n_cols=a.n))


def test_perm_not_bijective_duplicate_target():
    a, ws = _solo()
    p = ws.inv_perm.copy()
    p[1] = p[0]
    bad = dataclasses.replace(ws, inv_perm=p)
    assert "perm_not_bijective" in _kinds(
        verify_workspace(bad, n_cols=a.n))


def test_perm_not_bijective_out_of_range():
    a, ws = _solo()
    p = ws.inv_perm.copy()
    p[0] = ws.ws_rows + 7
    bad = dataclasses.replace(ws, inv_perm=p)
    assert "perm_not_bijective" in _kinds(
        verify_workspace(bad, n_cols=a.n))


def test_perm_roundtrip_stale_staged_row_map():
    from repro.core.plan import workspace_row_map
    a, ws = _solo()
    rm = workspace_row_map(ws.inv_perm, ws.ws_rows)
    # the shipped constant verifies...
    assert _kinds(verify_workspace(ws, n_cols=a.n, row_map=rm)) == set()
    # ...but a stale/corrupted staged map does not invert inv_perm
    stale = rm.copy()
    stale[int(ws.inv_perm[0])] = stale[int(ws.inv_perm[1])]
    assert "perm_roundtrip" in _kinds(
        verify_workspace(ws, n_cols=a.n, row_map=stale))
    # wrong-sized maps are caught before indexing
    assert "perm_roundtrip" in _kinds(
        verify_workspace(ws, n_cols=a.n, row_map=rm[:-1]))


def test_dma_window_undersized():
    a, ws = _solo(density=0.3)
    assert ws.max_span > 1
    span, = [int(np.max(np.where(ws.blk_tag == 1,
                                 ws.blk_L.astype(np.int64)
                                 * ws.row_block * ws.bk,
                                 ws.blk_L.astype(np.int64)
                                 * ws.row_block)))]
    assert span > 1, "need a real extent wider than the shrunk window"
    bad = dataclasses.replace(ws, max_span=1)
    assert "dma_window" in _kinds(verify_workspace(bad, n_cols=a.n))


def test_mxu_alignment_unaligned_panels():
    a = _mixed_csr_for_verify()
    plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16)
    ws = build_fused_workspace(plan)
    assert _kinds(verify_workspace(ws, n_cols=a.n)) == set()
    mxu = np.flatnonzero(ws.blk_tag == MXU_TAG)
    assert mxu.size
    off = ws.blk_off.copy()
    off[mxu] -= 8              # still monotone and in bounds, unaligned
    bad = dataclasses.replace(ws, blk_off=off)
    assert "mxu_alignment" in _kinds(verify_workspace(bad, n_cols=a.n))


def test_merge_alignment_width_not_dividing_table():
    a, ws = _solo()
    w = next(w for w in (3, 5, 7) if ws.num_blocks % w)
    bad = dataclasses.replace(ws, merge_width=w,
                              blk_span=None, blk_cspan=None)
    assert "merge_alignment" in _kinds(verify_workspace(bad, n_cols=a.n))


def test_gather_oob_past_sentinel():
    a, ws = _solo()
    assert ws.nnz == a.nnz      # stamped by the packer
    g = ws.gather_flat.copy()
    g[0] = a.nnz + 5            # neither real [0, nnz) nor sentinel
    bad = dataclasses.replace(ws, gather_flat=g)
    assert "gather_oob" in _kinds(verify_workspace(bad, n_cols=a.n))


def test_gather_check_skipped_when_nnz_unknown():
    a, ws = _solo()
    g = ws.gather_flat.copy()
    g[0] = a.nnz + 5
    bad = dataclasses.replace(ws, gather_flat=g, nnz=-1)
    assert "gather_oob" not in _kinds(verify_workspace(bad, n_cols=a.n))
    # the override argument re-enables it for hand-built workspaces
    assert "gather_oob" in _kinds(
        verify_workspace(bad, nnz=a.nnz, n_cols=a.n))


def test_cols_oob_referenced_entry():
    a, ws = _solo()
    real = np.flatnonzero(ws.blk_L > 0)
    c = ws.cols_flat.copy()
    c[int(ws.blk_coff[real[0]])] = 10**6
    bad = dataclasses.replace(ws, cols_flat=c)
    assert "cols_oob" in _kinds(verify_workspace(bad, n_cols=a.n))
    # without n_cols there is nothing to bound against: skipped
    assert "cols_oob" not in _kinds(verify_workspace(bad))


# -- sharded mutations -------------------------------------------------------


def test_sharded_bounds_malformed():
    a, sw = _sharded()
    b = np.asarray(sw.bounds).copy()
    b[1] = b[-1] + 3            # no longer monotone
    bad = dataclasses.replace(sw, bounds=b)
    assert "splits_malformed" in _kinds(
        verify_workspace(bad, n_cols=a.n))


def test_sharded_perm_region_cross_chip_swap():
    a, sw = _sharded()
    b = np.asarray(sw.bounds)
    assert b[1] > 0 and b[2] > b[1]
    p = sw.inv_perm.copy()
    i, j = 0, int(b[1])         # one row per chip, swapped
    p[i], p[j] = p[j], p[i]
    bad = dataclasses.replace(sw, inv_perm=p)
    assert "perm_region" in _kinds(verify_workspace(bad, n_cols=a.n))


def test_xshard_stale_fetch_table():
    a, sw = _sharded(n_chips=2, x_sharding="rows")
    assert sw.x_fetch is not None
    xf = sw.x_fetch.copy()
    xf[0, 0] = xf[0, 0] + 1     # chip 0's panel list no longer matches
    bad = dataclasses.replace(sw, x_fetch=xf)
    assert "xshard_fetch" in _kinds(verify_workspace(bad, n_cols=a.n))


# -- batched mutations -------------------------------------------------------


def test_batched_splits_malformed():
    mats, bw = _batched()
    rs = np.asarray(bw.row_splits).copy()
    rs[1] = rs[-1] + 9
    bad = dataclasses.replace(bw, row_splits=rs)
    assert "splits_malformed" in _kinds(verify_workspace(bad))


def test_batched_perm_region_cross_request_swap():
    mats, bw = _batched()
    rs = np.asarray(bw.row_splits)
    p = bw.inv_perm.copy()
    i, j = 0, int(rs[1])        # a row of request 0 and one of request 1
    p[i], p[j] = p[j], p[i]
    bad = dataclasses.replace(bw, inv_perm=p)
    assert "perm_region" in _kinds(verify_workspace(bad))


def test_batched_gather_crosses_request_boundary():
    mats, bw = _batched()
    vs = np.asarray(bw.val_splits)
    assert vs[1] < vs[-1]
    g = bw.gather_flat.copy()
    g[0] = vs[1]                # request 0 slot reading request 1 vals
    bad = dataclasses.replace(bw, gather_flat=g)
    assert "gather_oob" in _kinds(verify_workspace(bad))


# -- attention contracts -----------------------------------------------------


def test_attn_mask_negative_weight():
    out = verify_attention_contract(
        SPARSE_ATTN_MIXED_EINSUM, np.array([0.5, -1.0, 2.0]))
    assert "attn_mask_negative" in _kinds(out)


def test_attn_mask_nan_weight():
    out = verify_attention_contract(
        SPARSE_ATTN_MIXED_EINSUM, np.array([0.5, np.nan]))
    assert "attn_mask_negative" in _kinds(out)


def test_attn_spec_missing_operands():
    bad = dataclasses.replace(SPARSE_ATTN_MIXED_EINSUM, col_operands=1)
    assert "attn_spec" in _kinds(verify_attention_contract(bad))


def test_attn_spec_mixed_mismatch():
    """The attention spec carries no tagging flag to mismatch: an
    MXU-tagged attention workspace meets the attention contract."""
    a = _mixed_csr_for_verify()
    ws = build_einsum_workspace(SPARSE_ATTN_MIXED_EINSUM, a.row_ptr,
                                a.col_indices, a.shape, 16)
    assert ws.has_mxu
    out = verify_workspace(ws, n_cols=a.n, spec=SPARSE_ATTN_MIXED_EINSUM,
                           vals=np.ones(a.nnz))
    assert _kinds(out) == set()


# -- clean round-trips: real pipeline artifacts carry zero errors ------------


@pytest.mark.parametrize("family", ["uniform", "powerlaw", "banded"])
@pytest.mark.parametrize("mixed", [False, True])
@pytest.mark.parametrize("merge_threshold", [0, 8])
def test_clean_solo(family, mixed, merge_threshold):
    a, ws = _solo(family=family, mixed=mixed,
                  merge_threshold=merge_threshold, density=0.12)
    assert _kinds(verify_workspace(ws, n_cols=a.n)) == set()
    check_workspace(ws, n_cols=a.n)     # and the raising door agrees


@pytest.mark.parametrize("backend", ["vpu", "pallas_bcsr"])
@pytest.mark.parametrize("x_sharding", ["replicated", "rows"])
@pytest.mark.parametrize("n_chips", [2, 4])
def test_clean_sharded(backend, x_sharding, n_chips):
    a, sw = _sharded(n_chips=n_chips,
                     mxu_gain=0.0 if backend == "vpu" else 4.0,
                     x_sharding=x_sharding)
    assert _kinds(verify_workspace(sw, n_cols=a.n)) == set()
    check_workspace(sw, n_cols=a.n)


def test_clean_batched():
    mats, bw = _batched()
    assert _kinds(verify_workspace(bw)) == set()
    check_workspace(bw)


def test_clean_property_sweep():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=15, deadline=None)
    @hyp.given(
        m=st.integers(min_value=8, max_value=80),
        n=st.integers(min_value=8, max_value=80),
        density=st.floats(min_value=0.02, max_value=0.4),
        seed=st.integers(min_value=0, max_value=2**16),
        mixed=st.booleans(),
        merge_threshold=st.sampled_from([0, 4, 16]))
    def run(m, n, density, seed, mixed, merge_threshold):
        a, ws = _solo(m=m, n=n, density=density, seed=seed,
                      mixed=mixed, merge_threshold=merge_threshold)
        assert _kinds(verify_workspace(ws, n_cols=a.n)) == set()

    run()


# -- check_workspace / resolve_validate contracts ----------------------------


def test_check_workspace_raises_with_violations():
    a, ws = _solo()
    p = ws.inv_perm.copy()
    p[1] = p[0]
    bad = dataclasses.replace(ws, inv_perm=p)
    with pytest.raises(PlanVerificationError) as ei:
        check_workspace(bad, n_cols=a.n, context="unit")
    err = ei.value
    assert err.violations and all(v.severity == "error"
                                  for v in err.violations)
    assert "perm_not_bijective" in str(err) and "unit" in str(err)


def test_check_workspace_off_is_a_no_op_even_on_garbage():
    a, ws = _solo()
    bad = dataclasses.replace(
        ws, blk_off=ws.blk_off + np.int32(10**6))
    check_workspace(bad, n_cols=a.n, level="off")   # must not raise
    with pytest.raises(PlanVerificationError):
        check_workspace(bad, n_cols=a.n, level="cheap")


def test_cheap_level_skips_stream_scans():
    a, ws = _solo()
    g = ws.gather_flat.copy()
    g[0] = a.nnz + 5
    bad = dataclasses.replace(ws, gather_flat=g)
    assert _kinds(verify_workspace(bad, n_cols=a.n,
                                   level="cheap")) == set()
    assert "gather_oob" in _kinds(
        verify_workspace(bad, n_cols=a.n, level="full"))


def test_resolve_validate():
    assert resolve_validate(None, interpret=True) == "full"
    assert resolve_validate("auto", interpret=False) == "off"
    for mode in VALIDATE_MODES:
        assert resolve_validate(mode, interpret=False) == mode
    with pytest.raises(ValueError):
        resolve_validate("sometimes")


def test_verify_workspace_rejects_unknown_types():
    with pytest.raises(TypeError):
        verify_workspace(object())
    a, ws = _solo()
    with pytest.raises(ValueError):
        verify_workspace(ws, level="paranoid")


# -- the compile front door refuses a malformed instance ---------------------


def test_compile_rejects_out_of_bounds_structure():
    # CSRMatrix asserts shape consistency but NOT column bounds — a
    # natural producer bug the verifier must stop before dispatch
    jnp = pytest.importorskip("jax.numpy")
    from repro.core.spmm import compile_spmm
    m, n, nnz = 16, 16, 8
    rng = np.random.default_rng(3)
    row_ptr = np.zeros(m + 1, np.int64)
    row_ptr[1:] = np.cumsum(np.bincount(
        rng.integers(0, m, nnz), minlength=m))
    cols = rng.integers(0, n, nnz).astype(np.int32)
    cols[0] = n + 4             # out of bounds
    a = CSRMatrix((m, n), row_ptr, cols, jnp.ones(nnz))
    with pytest.raises(PlanVerificationError) as ei:
        # backend pinned to a fused path: "auto" on CPU picks the ref
        # backend, which has no plan IR to verify
        compile_spmm(a, 8, backend="pallas_bcsr", mxu_gain=0.0,
                     interpret=True, validate="full", autotune=False)
    assert any(v.kind == "cols_oob" for v in ei.value.violations)
