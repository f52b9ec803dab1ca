"""Multi-chip fused dispatch: shard_map over a 1-D chip mesh.

Covers the PR's acceptance criteria:
  * exactly n_chips pallas dispatches per forward (DISPATCH_COUNTS),
    eagerly and at jit trace time,
  * sharded output is bit-identical to the single-chip fused path for
    all three strategies,
  * the mesh is part of the jit-cache key,
  * gradients flow through the sharded forward,
  * non-fused backends reject mesh/n_chips.

In-process tests size the chip count to whatever devices exist (1 on a
plain CPU run); the subprocess test forces an 8-device host mesh so the
full acceptance criterion runs even from a single-device session.  CI
additionally runs the whole suite under
XLA_FLAGS=--xla_force_host_platform_device_count=8.
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

from repro.core import (CSRMatrix, chip_mesh, compile_spmm, random_csr,
                        resolve_chip_mesh, spmm)
from repro.core.jit_cache import JitCache, mesh_fingerprint
from repro.core.plan import STRATEGIES
from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[1]
N_DEV = len(jax.devices())
MAX_CHIPS = min(N_DEV, 4)
# the fused backend with every block-row tagged VPU (ELL gather+FMA)
VPU = dict(backend="pallas_bcsr", mxu_gain=0.0)


def _skewed_csr(seed=0):
    """Same shape family as test_fused_ell: 32 light rows + 8 heavy rows
    so nnz_split provably multi-segments (and chips see unequal rows)."""
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
def test_sharded_bit_matches_unsharded(strategy):
    a = _skewed_csr(seed=2)
    x = _x(a.n, 16, seed=3)
    y0 = spmm(a, x, strategy=strategy, **VPU,
              interpret=True, cache=JitCache())
    y = spmm(a, x, strategy=strategy, **VPU,
             interpret=True, n_chips=MAX_CHIPS, cache=JitCache())
    assert np.array_equal(np.asarray(y), np.asarray(y0))


def test_one_dispatch_per_chip_eager():
    a = _skewed_csr(seed=4)
    x = _x(a.n, 16, seed=5)
    c = compile_spmm(a, 16, strategy="nnz_split", **VPU,
                     interpret=True, n_chips=MAX_CHIPS, cache=JitCache())
    vals = jnp.asarray(a.vals)
    ops.reset_dispatch_counts()
    c(vals, x)
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == MAX_CHIPS
    assert ops.DISPATCH_COUNTS["bcsr_fused_sharded"] == 1
    c(vals, x)
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == 2 * MAX_CHIPS


def test_one_dispatch_per_chip_under_jit():
    """Compiled mode: tracing issues the n_chips dispatches once; the
    compiled executable then reuses them (Table IV: the artifact is
    built once per instance, not per call)."""
    a = _skewed_csr(seed=6)
    x = _x(a.n, 16, seed=7)
    c = compile_spmm(a, 16, strategy="nnz_split", **VPU,
                     interpret=True, n_chips=MAX_CHIPS, cache=JitCache())
    vals = jnp.asarray(a.vals)
    fwd = jax.jit(lambda v, xx: c(v, xx))
    ops.reset_dispatch_counts()
    y = fwd(vals, x)
    jax.block_until_ready(y)
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == MAX_CHIPS   # trace-time
    y2 = fwd(vals, x)
    jax.block_until_ready(y2)
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == MAX_CHIPS   # cached exec
    assert np.array_equal(np.asarray(y), np.asarray(y2))


def _iter_eqns(jaxpr):
    """All equations in a jaxpr, recursing into sub-jaxprs (pjit bodies,
    shard_map bodies, scan/while carries...) via duck typing so it works
    across jax versions."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = v if hasattr(v, "eqns") else getattr(v, "jaxpr", None)
            if hasattr(inner, "eqns"):
                yield from _iter_eqns(inner)


def test_sharded_trace_is_one_pallas_call_inside_shard_map():
    """Structural twin of the DISPATCH_COUNTS assertion, measured on the
    traced program rather than the host counter: the sharded forward
    must lower to exactly ONE shard_map over the chip mesh whose body
    holds exactly ONE pallas_call (SPMD replication then executes it
    once per chip), with no pallas_call outside it."""
    a = _skewed_csr(seed=10)
    x = _x(a.n, 16, seed=11)
    c = compile_spmm(a, 16, strategy="nnz_split", **VPU,
                     interpret=True, n_chips=MAX_CHIPS, cache=JitCache())
    vals = jnp.asarray(a.vals)
    jaxpr = jax.make_jaxpr(lambda v, xx: c(v, xx))(vals, x)
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


def test_sharded_gradients_match_dense():
    a = _skewed_csr(seed=8)
    d = 12
    x = _x(a.n, d, seed=9)
    c = compile_spmm(a, d, strategy="nnz_split", **VPU,
                     interpret=True, n_chips=MAX_CHIPS, cache=JitCache())
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


def test_cache_key_distinguishes_mesh():
    a = random_csr(16, 16, density=0.2, family="uniform", seed=9)
    cache = JitCache()
    c0 = compile_spmm(a, 8, **VPU, interpret=True,
                      cache=cache)
    c1 = compile_spmm(a, 8, **VPU, interpret=True,
                      n_chips=1, cache=cache)
    assert c0 is not c1                       # unsharded != 1-chip mesh
    assert cache.stats()["entries"] == 2
    # equivalent spellings (n_chips vs explicit mesh) share one artifact
    c2 = compile_spmm(a, 8, **VPU, interpret=True,
                      mesh=chip_mesh(1), cache=cache)
    assert c2 is c1
    assert cache.stats()["entries"] == 2


def test_mesh_fingerprint_and_resolution():
    assert mesh_fingerprint(None) is None
    assert resolve_chip_mesh(None, None) is None
    m1 = chip_mesh(1)
    assert mesh_fingerprint(m1) == (("chips",), (0,))
    assert resolve_chip_mesh(m1, 1) is m1
    with pytest.raises(ValueError):
        resolve_chip_mesh(m1, 2)             # n_chips != mesh size
    with pytest.raises(ValueError):
        chip_mesh(N_DEV + 1)                 # more chips than devices
    with pytest.raises(ValueError):
        chip_mesh(0)


@pytest.mark.parametrize("backend", ["ref", "dense"])
def test_sharding_rejects_non_fused_backends(backend):
    a = random_csr(16, 16, density=0.2, family="uniform", seed=3)
    with pytest.raises(ValueError):
        compile_spmm(a, 8, backend=backend, interpret=True, n_chips=1,
                     cache=JitCache())


def test_sharding_accepts_bcsr_backend():
    """The mixed VPU/MXU plan shards: MXU block-rows and a chip mesh
    go together."""
    a = random_csr(16, 16, density=0.2, family="uniform", seed=3)
    c = compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                     n_chips=1, cache=JitCache())
    assert c.backend == "pallas_bcsr" and c.n_chips == 1


def test_auto_backend_resolves_fused_when_sharded():
    """backend="auto" + a sharding request must resolve to the fused
    pallas_bcsr on every host (via interpret off the chip) — never the
    single-device ref backend, which would reject the mesh."""
    a = _skewed_csr(seed=12)
    x = _x(a.n, 8, seed=13)
    c = compile_spmm(a, 8, backend="auto", n_chips=1, cache=JitCache())
    assert c.backend == "pallas_bcsr" and c.n_chips == 1
    y = spmm(a, x, backend="auto", n_chips=1, cache=JitCache())
    y_ref = spmm(a, x, backend="ref", cache=JitCache())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_acceptance_on_8_device_mesh():
    """The ISSUE's acceptance criterion, end to end on a forced 8-device
    host mesh: bit-identity with the single-chip fused path for all
    three strategies, and exactly 8 dispatches per forward."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    code = textwrap.dedent("""
        import jax, numpy as np, jax.numpy as jnp
        assert len(jax.devices()) == 8
        from repro.core import random_csr, spmm
        from repro.core.jit_cache import JitCache
        from repro.core.plan import STRATEGIES
        from repro.kernels import ops
        a = random_csr(128, 96, density=0.06, family="powerlaw", seed=0)
        x = jnp.asarray(np.random.default_rng(1)
                        .standard_normal((a.n, 20)), jnp.float32)
        for strategy in STRATEGIES:
            y0 = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
                      mxu_gain=0.0, interpret=True, cache=JitCache())
            ops.reset_dispatch_counts()
            y8 = spmm(a, x, strategy=strategy, backend="pallas_bcsr",
                      mxu_gain=0.0, interpret=True, n_chips=8,
                      cache=JitCache())
            assert ops.DISPATCH_COUNTS["bcsr_fused"] == 8, strategy
            assert np.array_equal(np.asarray(y0), np.asarray(y8)), strategy
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
