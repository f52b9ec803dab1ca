"""Mixed VPU/MXU fused dispatch (backend=pallas_bcsr after the BCSR
fold-in) — the acceptance suite for the descriptor-stream unification.

Covers the PR's acceptance criteria:
  * the mixed plan genuinely mixes (both tags present) on a structure
    with dense block-rows AND ragged sparse rows,
  * the mixed plan == the all-VPU plan (mxu_gain=0) == ref oracle
    across all three strategies,
  * sharded-BCSR is BIT-identical to single-chip fused-BCSR,
  * gradients through the MXU path match the dense oracle,
  * exactly ONE pallas_call per chip for a mixed plan, asserted BOTH
    via DISPATCH_COUNTS and on the traced jaxpr (one shard_map whose
    body holds one pallas_call),
  * chip partition boundaries are block-row aligned for the mixed path,
  * the 8-device subprocess acceptance run.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CSRMatrix, MXU_TAG, VPU_TAG, build_mixed_plan,
                        build_fused_workspace, build_sharded_workspace,
                        compile_spmm, partition_rows_for_chips, random_csr,
                        spmm)
from repro.core.jit_cache import JitCache
from repro.core.plan import STRATEGIES
from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[1]
N_DEV = len(jax.devices())
MAX_CHIPS = min(N_DEV, 4)


def _mixed_csr(seed=0, m=48, n=64):
    """Dense banded block-rows (MXU bait) + 1-2 nnz ragged rows (VPU
    bait): the structure the mixed tagging heuristic exists for."""
    rng = np.random.default_rng(seed)
    dense = np.zeros((m, n), np.float32)
    for i in range(16):                      # two dense block-rows
        j0 = (i // 8) * 16
        dense[i, j0:j0 + 16] = rng.standard_normal(16)
    for i in range(16, m):                   # ragged sparse tail
        k = rng.integers(1, 3)
        dense[i, rng.choice(n, size=k, replace=False)] = (
            rng.standard_normal(k))
    return CSRMatrix.from_dense(dense)


def _x(n, d, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((n, d)), jnp.float32)


def test_mixed_plan_has_both_tags():
    a = _mixed_csr()
    plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16)
    ws = build_fused_workspace(plan)
    assert np.any(ws.blk_tag == MXU_TAG), "dense block-rows must go MXU"
    assert np.any(ws.blk_tag == VPU_TAG), "ragged rows must stay VPU"
    assert 0 < plan.mxu_share < 1
    assert 0 < plan.efficiency <= 1
    # every output row lands exactly once inside the workspace
    assert len(set(ws.inv_perm.tolist())) == a.m
    assert np.all(ws.inv_perm < ws.ws_rows)


def test_mxu_gain_extremes_force_pure_plans():
    a = _mixed_csr(seed=1)
    pure_vpu = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16,
                                mxu_gain=0.0)
    assert not pure_vpu.mxu_rows and pure_vpu.mxu_share == 0.0
    pure_mxu = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16,
                                mxu_gain=float("inf"))
    assert not pure_mxu.vpu_rows.size and pure_mxu.mxu_share == 1.0


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_mixed_fused_matches_ref_and_ell(strategy):
    a = _mixed_csr(seed=2)
    x = _x(a.n, 20, seed=3)
    y_ref = spmm(a, x, strategy=strategy, backend="ref", cache=JitCache())
    y_vpu = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
                 mxu_gain=0.0, interpret=True, cache=JitCache())
    y = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
             interpret=True, cache=JitCache())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_vpu),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("family", ("uniform", "powerlaw", "banded"))
def test_mixed_fused_matches_ref_random_families(family):
    a = random_csr(35, 50, density=0.15, family=family, seed=11)
    x = _x(a.n, 24, seed=12)
    y_ref = spmm(a, x, backend="ref", cache=JitCache())
    y = spmm(a, x, backend="pallas_bcsr", interpret=True,
             cache=JitCache())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_single_dispatch_for_mixed_plan():
    a = _mixed_csr(seed=4)
    x = _x(a.n, 16, seed=5)
    c = compile_spmm(a, 16, backend="pallas_bcsr", interpret=True,
                     cache=JitCache())
    assert c.mixed_plan.mxu_rows and c.mixed_plan.vpu_rows.size
    ops.reset_dispatch_counts()
    c(jnp.asarray(a.vals), x)
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == 1


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sharded_bcsr_bit_matches_unsharded(strategy):
    a = _mixed_csr(seed=6)
    x = _x(a.n, 16, seed=7)
    y0 = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
              interpret=True, cache=JitCache())
    y = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
             interpret=True, n_chips=MAX_CHIPS, cache=JitCache())
    assert np.array_equal(np.asarray(y), np.asarray(y0)), strategy


def test_one_dispatch_per_chip_mixed():
    a = _mixed_csr(seed=8)
    x = _x(a.n, 16, seed=9)
    c = compile_spmm(a, 16, backend="pallas_bcsr", interpret=True,
                     n_chips=MAX_CHIPS, cache=JitCache())
    assert c.sharded_workspace.has_mxu
    vals = jnp.asarray(a.vals)
    ops.reset_dispatch_counts()
    c(vals, x)
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == MAX_CHIPS
    assert ops.DISPATCH_COUNTS["bcsr_fused_sharded"] == 1
    c(vals, x)
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == 2 * MAX_CHIPS


def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = v if hasattr(v, "eqns") else getattr(v, "jaxpr", None)
            if hasattr(inner, "eqns"):
                yield from _iter_eqns(inner)


def test_mixed_sharded_trace_is_one_pallas_call_per_chip():
    """Jaxpr twin of the DISPATCH_COUNTS assertion for the MIXED plan:
    exactly one shard_map over the chip mesh whose body holds exactly
    one pallas_call — SPMD replication then executes it once per chip,
    VPU and MXU blocks together."""
    a = _mixed_csr(seed=10)
    x = _x(a.n, 16, seed=11)
    c = compile_spmm(a, 16, backend="pallas_bcsr", interpret=True,
                     n_chips=MAX_CHIPS, cache=JitCache())
    assert c.sharded_workspace.has_mxu
    jaxpr = jax.make_jaxpr(lambda v, xx: c(v, xx))(
        jnp.asarray(a.vals), x)
    eqns = list(_iter_eqns(jaxpr.jaxpr))
    shard_eqns = [e for e in eqns if e.primitive.name == "shard_map"]
    assert len(shard_eqns) == 1
    mesh_param = shard_eqns[0].params.get("mesh")
    if hasattr(mesh_param, "size"):
        assert mesh_param.size == MAX_CHIPS
    pallas = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(pallas) == 1
    body = shard_eqns[0].params["jaxpr"]
    body = body if hasattr(body, "eqns") else body.jaxpr
    in_body = [e for e in _iter_eqns(body)
               if e.primitive.name == "pallas_call"]
    assert len(in_body) == 1


def test_mixed_gradients_match_dense():
    """Gradient flow THROUGH the MXU path: d(vals) via sddmm and d(x)
    via the transposed mixed plan must match the dense oracle."""
    a = _mixed_csr(seed=12)
    d = 12
    x = _x(a.n, d, seed=13)
    c = compile_spmm(a, d, backend="pallas_bcsr", interpret=True,
                     cache=JitCache())
    assert c.mixed_plan.mxu_rows            # the claim is non-trivial
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


def test_sharded_mixed_gradients_match_dense():
    a = _mixed_csr(seed=14)
    d = 8
    x = _x(a.n, d, seed=15)
    c = compile_spmm(a, d, backend="pallas_bcsr", interpret=True,
                     n_chips=MAX_CHIPS, cache=JitCache())
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


def test_partition_block_row_alignment():
    """The mixed path's chip partitioner must cut at block-row (not
    scalar-row) boundaries so no (bm x bk) block straddles a chip."""
    rng = np.random.default_rng(3)
    lengths = rng.integers(0, 9, size=100)
    row_ptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    for strategy in STRATEGIES:
        bounds = partition_rows_for_chips(row_ptr, 4, strategy, align=8)
        assert np.all(bounds[1:-1] % 8 == 0), (strategy, bounds)
        assert bounds[0] == 0 and bounds[-1] == 100
        assert np.all(np.diff(bounds) >= 0)


def test_sharded_mixed_workspace_bounds_aligned():
    a = _mixed_csr(seed=16, m=50)           # ragged tail: m % 8 != 0
    sw = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape, 16,
                                 n_chips=3)
    assert np.all(sw.bounds[1:-1] % sw.row_block == 0)
    assert sw.nnz == a.nnz
    assert len(set(sw.inv_perm.tolist())) == a.m
    assert 0 < sw.efficiency <= 1


def test_cache_key_distinguishes_mxu_gain():
    """bk/mxu_gain change the generated plan, so they are part of the
    artifact identity — two gains must not share a compiled artifact."""
    a = _mixed_csr(seed=17)
    cache = JitCache()
    c1 = compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                      mxu_gain=4.0, cache=cache)
    c2 = compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                      mxu_gain=0.0, cache=cache)
    assert c1 is not c2
    assert cache.stats()["entries"] == 2
    c3 = compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                      cache=cache)         # default gain hits c1
    assert c3 is c1


def test_acceptance_mixed_on_8_device_mesh():
    """ISSUE acceptance: a mixed VPU/MXU plan on an 8-device host mesh
    executes exactly n_chips fused dispatches, output allclose to ref,
    gradients matching the dense oracle."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    code = textwrap.dedent("""
        import jax, numpy as np, jax.numpy as jnp
        assert len(jax.devices()) == 8
        from repro.core import CSRMatrix, compile_spmm
        from repro.core.jit_cache import JitCache
        from repro.kernels import ops
        rng = np.random.default_rng(0)
        m, n, d = 80, 64, 20
        dense = np.zeros((m, n), np.float32)
        for i in range(32):
            j0 = (i // 8) * 16
            dense[i, j0:j0 + 16] = rng.standard_normal(16)
        for i in range(32, m):
            dense[i, rng.choice(n, 2, replace=False)] = (
                rng.standard_normal(2))
        a = CSRMatrix.from_dense(dense)
        x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        vals = jnp.asarray(a.vals)
        c = compile_spmm(a, d, backend="pallas_bcsr", interpret=True,
                         n_chips=8, cache=JitCache())
        assert c.sharded_workspace.has_mxu
        ops.reset_dispatch_counts()
        y = c(vals, x)
        assert ops.DISPATCH_COUNTS["bcsr_fused"] == 8
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(dense) @ np.asarray(x),
            rtol=1e-4, atol=1e-4)
        rows = np.repeat(np.arange(a.m), a.row_lengths)
        def loss(v, xx):
            return jnp.sum(jnp.tanh(c(v, xx)))
        def loss_dense(v, xx):
            dd = jnp.zeros(a.shape).at[rows, a.col_indices].set(v)
            return jnp.sum(jnp.tanh(dd @ xx))
        g = jax.grad(loss, argnums=(0, 1))(vals, x)
        gd = jax.grad(loss_dense, argnums=(0, 1))(vals, x)
        np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gd[0]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gd[1]),
                                   rtol=1e-4, atol=1e-4)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_gradient_sddmm_in_chunks_matches_one_pass(monkeypatch):
    """The values gradient runs its SDDMM in bounded chunks of nonzeros
    (the one-pass gather does not fit a chip at graph scale); chunking
    changes no bit."""
    import importlib
    spmm_mod = importlib.import_module("repro.core.spmm")
    a = random_csr(40, 50, density=0.2, family="powerlaw", seed=21)
    x = _x(a.n, 12, seed=22)
    vals = jnp.asarray(a.vals)

    def grads():
        c = compile_spmm(a, 12, backend="pallas_bcsr", interpret=True,
                         cache=JitCache())
        return jax.grad(lambda v, xx: jnp.sum(c(v, xx) ** 2),
                        argnums=(0, 1))(vals, x)

    g0 = grads()
    monkeypatch.setattr(spmm_mod, "_SDDMM_CHUNK", 7)
    g = grads()
    assert a.nnz > 7
    for want, got in zip(g0, g):
        assert np.array_equal(np.asarray(got), np.asarray(want))
