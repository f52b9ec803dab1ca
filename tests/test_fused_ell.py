"""Fused ELL (all-VPU) hot path + jit-cache correctness (deterministic;
no hypothesis needed — this is the tier-1 safety net for the serving
path).  The all-VPU plan is the fused backend tagged with
``mxu_gain=0``: every block-row an ELL gather+FMA trip, the layout the
Pokec cell runs on the chip.

Covers:
  * exactly ONE pallas dispatch per (matrix, d) instance, whatever the
    segment count (the paper's one-artifact-per-instance claim),
  * the fused all-VPU plan == ref backend on all three strategies,
    including a guaranteed multi-segment nnz_split plan,
  * interpret is part of every jit-cache key,
  * GLOBAL_CACHE-style concurrent access builds each key exactly once.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CSRMatrix, autotune_spmm, compile_batched_spmm,
                        compile_sparse_attention, compile_spmm, random_csr,
                        spmm)
from repro.core.jit_cache import JitCache
from repro.core.plan import (build_fused_workspace, build_mixed_plan,
                             build_plan, choose_merge_width)
from repro.kernels import ops
from repro.platform import STAGE_TILE, stage_limits

ROOT = Path(__file__).resolve().parents[1]

STRATEGIES = ("row_split", "nnz_split", "merge_split")
# the fused backend with every block-row tagged VPU
VPU = dict(backend="pallas_bcsr", mxu_gain=0.0)


def _skewed_csr(seed=0):
    """32 rows of 1 nnz + 8 rows of 64 nnz: nnz_split provably buckets
    this into >1 segment (separate padded cost 544 vs merged 2560)."""
    rng = np.random.default_rng(seed)
    m, n = 40, 80
    dense = np.zeros((m, n), np.float32)
    for i in range(32):
        dense[i, rng.integers(0, n)] = rng.standard_normal()
    for i in range(32, 40):
        cols = rng.choice(n, size=64, replace=False)
        dense[i, cols] = rng.standard_normal(64)
    return CSRMatrix.from_dense(dense)


def _x(n, d, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((n, d)), jnp.float32)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_single_dispatch_regardless_of_segment_count(strategy):
    a = _skewed_csr()
    x = _x(a.n, 16)
    c = compile_spmm(a, 16, strategy=strategy, interpret=True,
                     cache=JitCache(), **VPU)
    ops.reset_dispatch_counts()
    c(jnp.asarray(a.vals), x)
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == 1
    assert not c.mixed_plan.mxu_rows
    if strategy == "nnz_split":
        # the claim is non-trivial
        assert len(c.mixed_plan.vpu.segments) > 1


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fused_matches_ref_backend(strategy):
    a = _skewed_csr(seed=3)
    x = _x(a.n, 20, seed=4)
    y_ref = spmm(a, x, strategy=strategy, backend="ref", cache=JitCache())
    y = spmm(a, x, strategy=strategy, interpret=True, cache=JitCache(),
             **VPU)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_multi_segment_nnz_split_regression():
    """The fused path's correctness oracle on the exact shape the fusion
    exists for: a multi-segment nnz_split plan."""
    a = _skewed_csr(seed=7)
    plan = build_plan(a.row_ptr, a.col_indices, a.shape, 16,
                      strategy="nnz_split")
    assert len(plan.segments) > 1
    x = _x(a.n, 16, seed=8)
    y_ref = spmm(a, x, strategy="nnz_split", backend="ref",
                 cache=JitCache())
    y = spmm(a, x, strategy="nnz_split", interpret=True, cache=JitCache(),
             **VPU)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_workspace_descriptor_invariants():
    a = random_csr(50, 60, density=0.1, family="powerlaw", seed=2)
    for strategy in STRATEGIES:
        plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16,
                                strategy=strategy, mxu_gain=0.0)
        ws = build_fused_workspace(plan)
        bm = plan.row_block
        assert ws.ws_rows == ws.num_blocks * bm
        assert ws.cols_flat.shape == ws.gather_flat.shape
        # descriptors tile the real slot region exactly, in order; the
        # buffer additionally carries the max_span DMA tail so the
        # staged kernel's fixed window never runs out of bounds
        ends = ws.blk_off.astype(np.int64) + bm * ws.blk_L.astype(np.int64)
        assert ws.blk_off[0] == 0 if ws.num_blocks else True
        np.testing.assert_array_equal(ws.blk_off[1:], ends[:-1])
        assert ((ends[-1] if ws.num_blocks else 0)
                == ws.cols_flat.shape[0] - ws.max_cspan)
        assert ws.max_span == ws.max_cspan  # pure-VPU: streams parallel
        assert np.all(ws.blk_off + ws.max_span <= ws.gather_flat.shape[0])
        assert np.all(ws.blk_coff + ws.max_cspan <= ws.cols_flat.shape[0])
        # inv_perm hits every output row exactly once, inside workspace
        assert sorted(ws.inv_perm.tolist()) == sorted(set(
            ws.inv_perm.tolist()))
        assert len(ws.inv_perm) == a.m
        assert np.all(ws.inv_perm < max(ws.ws_rows, 1))


def test_fused_gradients_match_dense():
    a = _skewed_csr(seed=5)
    d = 12
    x = _x(a.n, d, seed=6)
    c = compile_spmm(a, d, strategy="nnz_split", interpret=True,
                     cache=JitCache(), **VPU)
    vals = jnp.asarray(a.vals)

    def loss(v, xx):
        return jnp.sum(jnp.tanh(c(v, xx)))

    rows = np.repeat(np.arange(a.m), a.row_lengths)

    def loss_dense(v, xx):
        dense = jnp.zeros(a.shape).at[rows, a.col_indices].set(v)
        return jnp.sum(jnp.tanh(dense @ xx))

    g = jax.grad(loss, argnums=(0, 1))(vals, x)
    gd = jax.grad(loss_dense, argnums=(0, 1))(vals, x)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gd[0]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gd[1]),
                               rtol=1e-4, atol=1e-4)


def test_cache_key_distinguishes_interpret():
    """Regression: a plan built with interpret=True must not be served
    for interpret=False calls (and vice versa)."""
    a = random_csr(16, 16, density=0.2, family="uniform", seed=9)
    cache = JitCache()
    c1 = compile_spmm(a, 8, interpret=True, cache=cache, **VPU)
    c2 = compile_spmm(a, 8, interpret=False, cache=cache, **VPU)
    assert c1 is not c2
    assert c1.interpret is True and c2.interpret is False
    assert cache.stats()["entries"] == 2
    # and the default (None) resolves to a concrete flag that hits one
    # of the two entries rather than minting a third artifact
    c3 = compile_spmm(a, 8, cache=cache, **VPU)
    assert c3 is (c1 if c3.interpret else c2)
    assert cache.stats()["entries"] == 2


BUILDERS = {
    "compile_spmm": lambda a, **kw: compile_spmm(a, 8, **kw),
    "compile_batched_spmm": lambda a, **kw: compile_batched_spmm(
        [a], 8, **kw),
    "compile_sparse_attention": lambda a, **kw: compile_sparse_attention(
        a, 8, **kw),
    "autotune_spmm": lambda a, **kw: autotune_spmm(a, 8, **kw),
}


@pytest.mark.parametrize("build", BUILDERS)
def test_unknown_backend_is_rejected_at_build(build):
    """A backend name outside BACKENDS fails when the artifact is built,
    with the names there are, not at its first call."""
    a = random_csr(16, 16, density=0.2, family="uniform", seed=4)
    with pytest.raises(ValueError,
                       match="backend must be one of .*pallas_bcsr"):
        BUILDERS[build](a, backend="pallas_ell", interpret=True,
                        cache=JitCache())


def test_jit_cache_single_flight_under_threads():
    cache = JitCache()
    builds = []
    barrier = threading.Barrier(8)
    results = []

    def builder():
        builds.append(1)
        return object()

    def worker():
        barrier.wait()
        results.append(cache.get_or_build(("k",), builder))

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(builds) == 1                       # single-flight
    assert len({id(r) for r in results}) == 1     # everyone got it
    st = cache.stats()
    assert st["entries"] == 1 and st["misses"] == 1
    assert st["hits"] == 7


def test_jit_cache_builder_failure_releases_key():
    cache = JitCache()
    with pytest.raises(RuntimeError):
        cache.get_or_build(("bad",), lambda: (_ for _ in ()).throw(
            RuntimeError("boom")))
    # key not poisoned: the next caller builds successfully
    assert cache.get_or_build(("bad",), lambda: "ok") == "ok"


# ---------------------------------------------------------------------------
# Per-block VPU lengths: each block of the packed stream runs its own
# rows' longest length, not its segment's.
# ---------------------------------------------------------------------------

def _bucketed_csr(seed=0, m=96, n=80):
    """Rows of many lengths, shuffled, plus two rows longer than a small
    staging window's pieces: nnz_split buckets them geometrically, and
    the blocks inside a bucket have different longest rows."""
    rng = np.random.default_rng(seed)
    lengths = np.concatenate([rng.integers(0, 48, m - 2), [70, 75]])
    rng.shuffle(lengths)
    dense = np.zeros((m, n), np.float32)
    for i, ln in enumerate(lengths):
        dense[i, rng.choice(n, size=int(ln), replace=False)] = (
            rng.standard_normal(int(ln)) + 2.0)
    return CSRMatrix.from_dense(dense)


def _segment_layout(plan):
    """The plan with every VPU block at its segment's length: the
    segment-wide layout the per-block one is checked against."""
    segs = [dataclasses.replace(s, blk_L=np.full_like(s.blk_L,
                                                      max(s.L, 1)))
            for s in plan.vpu.segments]
    return dataclasses.replace(
        plan, vpu=dataclasses.replace(plan.vpu, segments=segs))


def _block_steps(ws):
    """Loop steps of each descriptor's block, summed over the pieces
    up to and including it (a piece trip continues the trip before)."""
    W = ws.merge_width
    total = ws.blk_L.astype(np.int64)
    for t in np.flatnonzero(ws.blk_cont):  # a piece is its trip's first
        total[t * W] += total[(t - 1) * W]
    return total


@pytest.mark.parametrize("window", (None, 64))
@pytest.mark.parametrize("threshold", (0, 64))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_vpu_block_runs_its_longest_row(strategy, threshold, window,
                                        pack_with_window):
    """Every VPU block's ``blk_L``, summed over its pieces, is the
    longest row it covers (at least 1), and ``blk_L == 0`` marks inert
    pads alone: a descriptor that owns no row and that no piece trip
    continues."""
    a = _bucketed_csr(seed=1)
    plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16,
                            strategy=strategy, mxu_gain=0.0)
    mw = choose_merge_width(a.row_ptr, merge_threshold=threshold)
    ws = (build_fused_workspace(plan, merge_width=mw) if window is None
          else pack_with_window(plan, window, merge_width=mw))
    bm = ws.row_block
    owner = ws.inv_perm.astype(np.int64) // bm
    longest = np.ones(ws.num_blocks, np.int64)
    np.maximum.at(longest, owner, a.row_lengths)
    owned = np.zeros(ws.num_blocks, bool)
    owned[owner] = True
    np.testing.assert_array_equal(_block_steps(ws)[owned], longest[owned])
    assert np.all(ws.blk_tag == 0)
    # a piece's steps are whole windows but for the last
    continued = np.zeros(ws.num_blocks, bool)
    continued[(np.flatnonzero(ws.blk_cont) - 1) * mw] = True
    np.testing.assert_array_equal(ws.blk_L == 0, ~(owned | continued))
    if window is not None:
        assert ws.blk_cont.any()
        assert np.all(ws.blk_L[continued] == window // bm)
    assert (mw > 1) == (threshold > 0)
    # the streams, block by block and piece by piece, as a loop packs
    # them: each piece a (bm, <= CL) panel of the block's first L lanes
    CL = (window or stage_limits().window) // bm
    cols, gather = [], []
    for seg in plan.vpu.segments:
        for b, L in enumerate(seg.blk_L):
            rows = slice(b * bm, (b + 1) * bm)
            for lo in range(0, L, CL):
                lanes = slice(lo, min(lo + CL, L))
                cols.append(seg.cols_pad[rows, lanes].reshape(-1))
                gather.append(seg.gather_idx[rows, lanes].reshape(-1))
    cols, gather = np.concatenate(cols), np.concatenate(gather)
    np.testing.assert_array_equal(ws.cols_flat[:cols.size], cols)
    np.testing.assert_array_equal(ws.gather_flat[:gather.size], gather)
    assert ws.gather_flat.size - ws.max_span == gather.size


def test_packed_stream_counts_each_blocks_longest_row():
    """On a length-bucketed skewed matrix the stream holds fewer slots
    than the segment layout, and ``vpu_steps`` is the sum over blocks
    of each block's longest row."""
    a = _bucketed_csr(seed=2)
    c = compile_spmm(a, 16, strategy="nnz_split", interpret=True,
                     cache=JitCache(), **VPU)
    plan = c.mixed_plan
    assert len(plan.vpu.segments) > 1
    lengths = a.row_lengths[plan.vpu_rows]
    want = 0
    for seg in plan.vpu.segments:
        lens = np.zeros(seg.R_pad, np.int64)
        lens[:seg.R] = lengths[seg.row_ids]
        want += int(np.maximum(lens.reshape(-1, 8).max(1), 1).sum())
    assert c.vpu_steps == want
    ws = build_fused_workspace(plan)
    old = build_fused_workspace(_segment_layout(plan))
    real = ws.gather_flat.shape[0] - ws.max_span
    assert real == 8 * want < old.gather_flat.shape[0] - old.max_span
    assert real < plan.padded_nnz
    # the artifact stages that stream, tail and whole tiles included
    assert c.vals_slots == -(-ws.gather_flat.shape[0] // STAGE_TILE) \
        * STAGE_TILE
    # the segment layout is what a block at its segment's length packs
    assert int(old.blk_L.sum()) * 8 == plan.padded_nnz


@pytest.mark.parametrize("threshold", (0, 64))
@pytest.mark.parametrize("staging", ("resident", "dma"))
def test_per_block_layout_is_bit_identical_to_segment_layout(
        staging, threshold):
    """Dropping each block's columns past its own longest row drops
    only trailing ``0 * X[0]`` terms: the kernel output equals the
    segment layout's bit for bit, and the artifact's equals ``ref``."""
    from test_staging import _run_workspace
    a = _bucketed_csr(seed=3)
    x = _x(a.n, 16, seed=4)
    plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16,
                            mxu_gain=0.0)
    mw = choose_merge_width(a.row_ptr, merge_threshold=threshold)
    y = _run_workspace(build_fused_workspace(plan, merge_width=mw), a, x,
                       staging)
    y_old = _run_workspace(
        build_fused_workspace(_segment_layout(plan), merge_width=mw), a, x,
        staging)
    assert np.array_equal(y, y_old)
    y_art = spmm(a, x, interpret=True, staging=staging,
                 merge_threshold=threshold, cache=JitCache(), **VPU)
    assert np.array_equal(np.asarray(y_art), y)
    y_ref = spmm(a, x, backend="ref", cache=JitCache())
    np.testing.assert_allclose(y, np.asarray(y_ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("staging", ("resident", "dma"))
def test_per_block_layout_matches_ref_on_1_to_8_chips(staging):
    """On a forced 8-device host mesh, the sharded all-VPU plan of a
    skewed matrix over 1, 3 and 8 chips, merged and not, equals the
    one-chip artifact bit for bit and ``ref`` within 1e-5."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"),
                                         str(ROOT / "tests")])
    code = textwrap.dedent(f"""
        import jax, numpy as np
        assert len(jax.devices()) == 8
        from repro.core import spmm
        from repro.core.jit_cache import JitCache
        from test_fused_ell import _bucketed_csr, _x
        a = _bucketed_csr(seed=5)
        x = _x(a.n, 16, seed=6)
        ref = np.asarray(spmm(a, x, backend="ref", cache=JitCache()))
        kw = dict(backend="pallas_bcsr", mxu_gain=0.0, interpret=True,
                  staging={staging!r})
        for threshold in (0, 64):
            y1 = np.asarray(spmm(a, x, merge_threshold=threshold,
                                 cache=JitCache(), **kw))
            np.testing.assert_allclose(y1, ref, rtol=1e-5, atol=1e-5)
            for chips in (1, 3, 8):
                y = np.asarray(spmm(a, x, merge_threshold=threshold,
                                    n_chips=chips, cache=JitCache(), **kw))
                assert np.array_equal(y, y1), (threshold, chips)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-4000:]


def _equal_length_csr(m=40, n=64, length=5, seed=0):
    rng = np.random.default_rng(seed)
    dense = np.zeros((m, n), np.float32)
    for i in range(m):
        dense[i, rng.choice(n, size=length, replace=False)] = 1.0 + i
    return CSRMatrix.from_dense(dense)


@pytest.mark.parametrize("case", ("all_mxu_attention", "equal_rows"))
def test_tables_equal_segment_layout_without_ragged_blocks(case):
    """Where no block is shorter than its segment, the packed tables
    are byte-identical to the segment layout: an all-MXU attention
    plan, which has no VPU segment, and an all-VPU plan whose rows
    share one length."""
    from repro.models.sparse_attention import sparse_attention_mask
    if case == "all_mxu_attention":
        a = sparse_attention_mask(128, window=16, num_global=4)
        plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16,
                                mxu_gain=float("inf"))
        assert not plan.vpu.segments and plan.mxu_rows
    else:
        a = _equal_length_csr()
        plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16,
                                mxu_gain=0.0)
        assert plan.vpu.segments and not plan.mxu_rows
    ws = build_fused_workspace(plan)
    old = build_fused_workspace(_segment_layout(plan))
    for f in ("blk_tag", "blk_off", "blk_coff", "blk_L", "blk_cont",
              "blk_span", "blk_cspan", "cols_flat", "gather_flat",
              "inv_perm"):
        got, want = getattr(ws, f), getattr(old, f)
        assert got.dtype == want.dtype and got.tobytes() == want.tobytes(), f


def test_merge_width_model_is_the_packed_median():
    """``choose_merge_width`` models each block's trips as its longest
    row over the length-sorted order.  For a one-segment nnz_split plan
    that is the packed stream: the width it picks at every threshold is
    the one the median packed ``blk_L`` gives."""
    rng = np.random.default_rng(7)
    lengths = rng.integers(16, 32, 200)
    a = _csr_of_lengths(lengths, n=64, seed=7)
    plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16,
                            strategy="nnz_split", mxu_gain=0.0)
    assert len(plan.vpu.segments) == 1
    ws = build_fused_workspace(plan)
    assert len(np.unique(ws.blk_L)) > 1
    typical = float(np.median(ws.blk_L))
    for threshold in range(1, int(typical) * 16 + 2):
        w = 1
        while w < 8 and typical * (w * 2) <= threshold:
            w *= 2
        assert choose_merge_width(a.row_ptr,
                                  merge_threshold=threshold) == w, threshold


def _csr_of_lengths(lengths, n, seed):
    rng = np.random.default_rng(seed)
    dense = np.zeros((len(lengths), n), np.float32)
    for i, ln in enumerate(lengths):
        dense[i, rng.choice(n, size=int(ln), replace=False)] = 1.0
    return CSRMatrix.from_dense(dense)


@pytest.mark.parametrize("kind", ("solo", "sharded", "batched",
                                  "attention", "ref"))
def test_vpu_steps_counter(kind):
    """``vpu_steps`` is the sum of ``blk_L`` over an artifact's VPU
    descriptors, fixed at build; None off the fused backends."""
    a = _bucketed_csr(seed=8)
    if kind == "attention":
        from repro.models.sparse_attention import sparse_attention_mask
        art = compile_sparse_attention(
            sparse_attention_mask(96, window=24, num_global=4), 16,
            backend="pallas_bcsr", bm=8, bk=8, mxu_gain=float("inf"),
            interpret=True, cache=JitCache())
    elif kind == "batched":
        art = compile_batched_spmm([a, a], 16, interpret=True,
                                   cache=JitCache(), **VPU)
    elif kind == "ref":
        art = compile_spmm(a, 16, backend="ref", cache=JitCache())
        assert art.vpu_steps is None
        return
    else:
        art = compile_spmm(a, 16, interpret=True, cache=JitCache(),
                           n_chips=1 if kind == "sharded" else None, **VPU)
    fw = art._tables()
    L, tag = np.asarray(fw.blk_L), np.asarray(fw.blk_tag)
    assert art.vpu_steps == int(L[tag == 0].sum())
    if kind == "attention":
        assert art.vpu_steps == 0
    else:
        solo = compile_spmm(a, 16, interpret=True, cache=JitCache(), **VPU)
        assert art.vpu_steps == solo.vpu_steps * (2 if kind == "batched"
                                                  else 1) > 0
