"""The slot-value stream a fused forward hands its kernel (DESIGN.md
§7.7): staged from the live lanes of lane-padded MXU panels, it must
equal ``concat(vals, [0])[gather_flat]`` bit for bit on every plan
shape, and gather every slot on a plan without MXU panels.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CSRMatrix, build_batched_workspace,
                        build_fused_workspace, build_mixed_plan,
                        build_sharded_workspace, build_workspace,
                        compile_batched_spmm,
                        compile_sparse_attention, compile_spmm, random_csr)
from repro.core.jit_cache import JitCache
from repro.core.spmm import _SlotValues, _slot_values, _tiles
from repro.models.sparse_attention import sparse_attention_mask
from repro.platform import LANE

ROOT = Path(__file__).resolve().parents[1]


def _mixed_matrix(seed=0):
    """Dense 8-row blocks (MXU) above ragged sparse rows (VPU)."""
    rng = np.random.default_rng(seed)
    dense = np.zeros((72, 80), np.float32)
    dense[:16, :24] = rng.uniform(0.5, 1.5, (16, 24))
    dense[40:48, 56:64] = rng.uniform(0.5, 1.5, (8, 8))
    for i in list(range(16, 40)) + list(range(48, 72)):
        cols = rng.choice(80, size=rng.integers(1, 4), replace=False)
        dense[i, cols] = rng.uniform(0.5, 1.5, cols.size)
    return CSRMatrix.from_dense(dense)


def _window_mask():
    return sparse_attention_mask(96, window=24, num_global=4)


def _vals(nnz, seed=1):
    """Distinct values with signed zeros among them, so a stream that
    differs only in a zero's sign fails the bitwise check."""
    v = np.random.default_rng(seed).standard_normal(nnz).astype(np.float32)
    v[::7] = -0.0
    return jnp.asarray(v)


def _bits(a):
    return np.asarray(a).view(np.uint32)


def _workspace(case, pack_with_window):
    """(host workspace, its nnz, members laid end to end)."""
    a = _window_mask() if case == "all_mxu" else _mixed_matrix()
    args = (a.row_ptr, a.col_indices, a.shape, 16)
    if case == "ell":          # the one-call composition, all VPU
        return build_workspace(*args, mxu_gain=0.0), a.nnz, 1
    if case == "bcsr_vpu":
        plan = build_mixed_plan(*args, mxu_gain=0.0)
        return build_fused_workspace(plan), a.nnz, 1
    if case in ("mixed", "all_mxu"):
        gain = float("inf") if case == "all_mxu" else 4.0
        plan = build_mixed_plan(*args, mxu_gain=gain)
        return build_fused_workspace(plan), a.nnz, 1
    if case == "merged":
        plan = build_mixed_plan(*args)
        return build_fused_workspace(plan, merge_width=4), a.nnz, 1
    if case == "pieces":
        # a window of one panel: every MXU block row with K > 1 splits
        plan = build_mixed_plan(*args)
        return pack_with_window(plan, 8 * LANE), a.nnz, 1
    if case == "batched":
        b = _window_mask()
        bw = build_batched_workspace(
            [(a.row_ptr, a.col_indices, a.shape),
             (b.row_ptr, b.col_indices, b.shape),
             (a.row_ptr, a.col_indices, a.shape)], 16)
        return bw, bw.nnz, bw.n_requests
    assert case == "sharded"
    sw = build_sharded_workspace(*args, n_chips=3)
    return sw, sw.nnz, 1


CASES = ("ell", "bcsr_vpu", "mixed", "all_mxu", "merged", "pieces",
         "batched", "sharded")


@pytest.mark.parametrize("case", CASES)
def test_staged_stream_is_the_element_gather(case, pack_with_window):
    ws, nnz, members = _workspace(case, pack_with_window)
    sv = _slot_values(ws, nnz, members)
    vals = _vals(nnz)
    g = _tiles(ws.gather_flat, nnz)
    want = jnp.concatenate([vals, jnp.zeros((1,), jnp.float32)])[g]
    got = sv.stage(vals)
    assert got.shape == want.shape == sv.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert sv.slots == g.size
    if case in ("ell", "bcsr_vpu"):
        assert sv.lanes is None and sv.gather_elems == sv.slots
    else:
        assert sv.lanes is not None
        assert sv.gather_elems < sv.slots
    if case == "all_mxu":
        assert sv.gather_elems <= sv.slots // 8
        assert sv.gather.shape[-1] == 0      # no VPU slots to gather


@pytest.mark.parametrize("case", ("mixed", "all_mxu", "pieces"))
def test_compact_split_skips_only_sentinel_lanes(case, pack_with_window):
    """What the compact staging leaves out is exactly the sentinel: the
    lanes past bk of its rows and the rows after its last live one."""
    ws, nnz, _ = _workspace(case, pack_with_window)
    sv = _slot_values(ws, nnz)
    rows = _tiles(ws.gather_flat, nnz).reshape(-1, LANE)
    p = sv.gather.shape[-1] // LANE
    e = rows.shape[0] - sv.zero_rows
    assert np.all(rows[p:, ws.bk:] == nnz)
    assert np.all(rows[e:] == nnz)
    np.testing.assert_array_equal(np.asarray(sv.lanes)[..., 0],
                                  rows[p:e, :ws.bk])
    # the compact rows begin at the first row past the last wide one
    assert p == 0 or np.any(rows[p - 1, ws.bk:] != nnz)


def test_artifacts_record_their_gathered_elements():
    a = _mixed_matrix()
    mask = _window_mask()
    ell = compile_spmm(a, 16, backend="pallas_bcsr", mxu_gain=0.0,
                       interpret=True, cache=JitCache())
    assert ell.vals_gather_elems == ell.vals_slots
    mixed = compile_spmm(a, 16, backend="pallas_bcsr", interpret=True,
                         cache=JitCache())
    assert mixed.vals_gather_elems < mixed.vals_slots
    attn = compile_sparse_attention(mask, 16, backend="pallas_bcsr",
                                    bm=8, bk=8, mxu_gain=float("inf"),
                                    interpret=True, cache=JitCache())
    assert attn.vals_gather_elems <= attn.vals_slots // 8
    batched = compile_batched_spmm([a, a], 16, backend="pallas_bcsr",
                                   interpret=True, cache=JitCache())
    assert batched.vals_gather_elems < batched.vals_slots
    sharded = compile_spmm(a, 16, backend="pallas_bcsr", interpret=True,
                           n_chips=1, cache=JitCache())
    assert sharded.vals_gather_elems < sharded.vals_slots
    arts = (ell, mixed, attn, batched, sharded)
    for art in arts:
        fw = (getattr(art, "_consts", None) or art._sharded
              or art._fused)
        assert art.vals_gather_elems == fw.vals.gather_elems
        assert art.vals_slots == fw.vals.slots
    ref = compile_spmm(a, 16, backend="ref", cache=JitCache())
    assert ref.vals_gather_elems is None and ref.vals_slots is None


@pytest.mark.parametrize("sharded", (False, True))
def test_spmm_output_matches_the_plain_gather(sharded):
    """The artifact's forward with the compact staging equals the same
    artifact fed the whole element gather, bit for bit."""
    a = _mixed_matrix(seed=3)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((80, 16)),
                    jnp.float32)
    art = compile_spmm(a, 16, backend="pallas_bcsr", interpret=True,
                       n_chips=1 if sharded else None, cache=JitCache())
    vals = _vals(a.nnz, seed=5)
    y = np.asarray(art(vals, x))
    name = "_sharded" if sharded else "_fused"
    fw = getattr(art, name)
    ws = art.sharded_workspace if sharded else build_fused_workspace(
        build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16))
    g = _tiles(ws.gather_flat, a.nnz)
    assert fw.vals.lanes is not None and fw.vals.shape == g.shape
    plain = _SlotValues(gather=jnp.asarray(g.astype(np.int32)),
                        shape=g.shape)
    setattr(art, name, dataclasses.replace(fw, vals=plain))
    np.testing.assert_array_equal(np.asarray(art(vals, x)), y)
    want = compile_spmm(a, 16, backend="ref", cache=JitCache())(vals, x)
    np.testing.assert_allclose(y, np.asarray(want), rtol=1e-5, atol=1e-5)


def test_pokec_like_plan_keeps_the_plain_gather():
    """An all-VPU mixed plan, as a power-law graph gets, stages its
    stream with the one element gather it always had."""
    a = random_csr(256, 256, density=0.02, family="powerlaw", seed=2)
    art = compile_spmm(a, 16, backend="pallas_bcsr", interpret=True,
                       mxu_gain=0.0, cache=JitCache())
    assert art._fused.vals.lanes is None
    assert art.vals_gather_elems == art.vals_slots
    assert art._fused.gather_flat.shape == (art.vals_slots,)


def test_chip_smoke_reads_the_staging_tables():
    """``chip_smoke.py`` logs and checks the slot-value tables through
    ``describe_fused`` and ``slot_table_devices``: on a 4-device host
    mesh the per-chip tables of a mixed and an all-VPU sharded plan sit
    on four devices, and the one-chip line names slots and gathers."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    code = textwrap.dedent("""
        import jax, numpy as np
        assert len(jax.devices()) == 4
        import chip_smoke
        from repro.core import CSRMatrix, compile_spmm, random_csr
        from repro.core.jit_cache import JitCache
        rng = np.random.default_rng(0)
        dense = np.zeros((96, 64), np.float32)
        for i in range(64):
            j0 = (i // 8) * 6
            dense[i, j0:j0 + 16] = 1.0 + rng.random(16)
        for i in range(64, 96):
            dense[i, rng.choice(64, 2, replace=False)] = 1.0
        mixed = CSRMatrix.from_dense(dense)
        vpu = random_csr(256, 256, density=0.02, family="powerlaw", seed=2)
        for a, gain, tables in ((mixed, 4.0, [4, 4]), (vpu, 0.0, [4])):
            c4 = compile_spmm(a, 16, backend="pallas_bcsr", interpret=True,
                              mxu_gain=gain, n_chips=4, cache=JitCache())
            assert chip_smoke.slot_table_devices(c4) == tables, a.shape
        c = compile_spmm(mixed, 16, backend="pallas_bcsr", interpret=True,
                         cache=JitCache())
        line = chip_smoke.describe_fused(c)
        assert c.vals_gather_elems < c.vals_slots
        assert (f"nnz={mixed.nnz} slots={c.vals_slots} "
                f"vpu_steps={c.vpu_steps} gathered={c.vals_gather_elems}"
                in line), line
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-4000:]
