"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracle
across shapes, dtypes, sparsity families and workload strategies."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CSRMatrix, compile_spmm, random_csr, spmm
from repro.core.jit_cache import JitCache
from repro.kernels.ref import sddmm_ref, spmm_csr_ref, spmm_dense_ref

FAMILIES = ("uniform", "powerlaw", "banded")
STRATEGIES = ("row_split", "nnz_split", "merge_split")
# the fused backend with every block-row tagged VPU (ELL gather+FMA)
VPU = dict(backend="pallas_bcsr", mxu_gain=0.0)


def _case(m, n, d, family, seed, dtype=jnp.float32, density=0.15):
    a = random_csr(m, n, density=density, family=family, seed=seed,
                   dtype=dtype)
    x = jnp.asarray(
        np.random.default_rng(seed + 1).standard_normal((n, d)), dtype)
    return a, x


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_pallas_vpu_matches_oracle(family, strategy):
    a, x = _case(33, 47, 20, family, seed=hash((family, strategy)) % 1000)
    y_ref = spmm_dense_ref(a.to_dense(), x)
    y = spmm(a, x, strategy=strategy, interpret=True, cache=JitCache(),
             **VPU)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(8, 8, 4), (16, 64, 8), (64, 16, 45),
                                   (40, 40, 128), (7, 130, 16)])
def test_pallas_vpu_shape_sweep(shape):
    m, n, d = shape
    a, x = _case(m, n, d, "uniform", seed=m * 7 + d)
    y_ref = spmm_dense_ref(a.to_dense(), x)
    y = spmm(a, x, interpret=True, cache=JitCache(), **VPU)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_vpu_dtypes(dtype):
    a, x = _case(24, 32, 16, "powerlaw", seed=5, dtype=dtype)
    y_ref = spmm_dense_ref(a.to_dense().astype(jnp.float32),
                           x.astype(jnp.float32))
    y = spmm(a, x, interpret=True, cache=JitCache(), **VPU)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("family", FAMILIES)
def test_pallas_bcsr_matches_oracle(family):
    a, x = _case(35, 50, 24, family, seed=11)
    y_ref = spmm_dense_ref(a.to_dense(), x)
    y = spmm(a, x, backend="pallas_bcsr", interpret=True, cache=JitCache())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_empty_rows_and_dense_row():
    # skewed: one dense row + many empty rows (the row_split worst case)
    m, n, d = 16, 32, 8
    dense = np.zeros((m, n), np.float32)
    dense[3] = np.random.default_rng(0).standard_normal(n)
    dense[7, :2] = 1.0
    a = CSRMatrix.from_dense(dense)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((n, d)),
                    jnp.float32)
    y_ref = spmm_dense_ref(jnp.asarray(dense), x)
    for strategy in STRATEGIES:
        for name, kw in (("vpu", VPU),
                         ("pallas_bcsr", {"backend": "pallas_bcsr"}),
                         ("ref", {"backend": "ref"})):
            y = spmm(a, x, strategy=strategy, interpret=True,
                     cache=JitCache(), **kw)
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"{strategy}/{name}")


def test_gradients_match_dense():
    a, x = _case(20, 28, 12, "uniform", seed=3)
    c = compile_spmm(a, 12, backend="ref", cache=JitCache())
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
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gd[1]),
                               rtol=1e-4, atol=1e-5)


def test_sddmm_oracle_consistency():
    a, x = _case(15, 21, 9, "banded", seed=9)
    dy = jnp.asarray(np.random.default_rng(2).standard_normal((15, 9)),
                     jnp.float32)
    got = sddmm_ref(a.row_ptr, a.col_indices, dy, x)
    rows = np.repeat(np.arange(a.m), a.row_lengths)
    full = np.asarray(dy) @ np.asarray(x).T
    want = full[rows, a.col_indices]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_ell_segment_ref_matches_csr_ref():
    a, x = _case(12, 18, 6, "uniform", seed=7)
    y1 = spmm_csr_ref(a.row_ptr, a.col_indices, jnp.asarray(a.vals), x, a.m)
    y2 = spmm_dense_ref(a.to_dense(), x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(12, 18, 9), (40, 33, 45), (8, 8, 128)])
def test_sddmm_pallas_matches_ref(shape):
    """The gradient's SDDMM (dvals = <dY[row], X[col]>) of a fused
    artifact, pulled back through jax.grad, against the oracle."""
    m, n, d = shape
    a, _ = _case(m, n, 4, "powerlaw", seed=m + d)
    rng = np.random.default_rng(0)
    dy = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    c = compile_spmm(a, d, backend="pallas_bcsr", interpret=True,
                     cache=JitCache())
    got = jax.grad(lambda v: jnp.sum(c(v, x) * dy))(jnp.asarray(a.vals))
    want = sddmm_ref(a.row_ptr, a.col_indices, dy, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_kernels_public_exports_importable():
    """Smoke: every symbol `repro.kernels` advertises in __all__ resolves,
    and the op wrappers (the dispatch-counting layer the rest of the
    stack calls) are importable — catches stale export lists."""
    import repro.kernels as kernels
    for name in kernels.__all__:
        assert getattr(kernels, name, None) is not None, name
    from repro.kernels.ops import (  # noqa: F401
        DISPATCH_COUNTS, attn_fused_op, attn_fused_sharded_op,
        default_interpret, reset_dispatch_counts, resolve_interpret,
        spmm_bcsr_fused_op, spmm_bcsr_fused_sharded_op)
    assert "spmm_bcsr_fused_sharded" in kernels.__all__
