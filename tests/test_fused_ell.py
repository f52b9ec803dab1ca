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
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CSRMatrix, autotune_spmm, compile_batched_spmm,
                        compile_sparse_attention, compile_spmm, random_csr,
                        spmm)
from repro.core.jit_cache import JitCache
from repro.core.plan import (build_fused_workspace, build_mixed_plan,
                             build_plan)
from repro.kernels import ops

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
