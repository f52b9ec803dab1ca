"""Property-based harness for the fused sparse-attention sandwich
(DESIGN.md §13), mirroring test_fused_properties.py's policy: hypothesis
generates adversarial mask structures — skewed, empty-row, single-row,
power-law — crossed with strategies, head/value widths and chip counts,
and asserts

  * fused sandwich == dense masked-softmax oracle (f64 numpy), forward,
  * the custom-VJP gradient == the ref backend's gradient (q, k, v and
    the mask weights),
  * DMA staging and sharding are bit-pure re-partitionings of the
    resident single-chip lowering.

Whole-module skip when hypothesis is absent (dev-only dependency; the
CI tier runs it).  Kernel-executing properties keep instances small:
every distinct structure is a fresh interpret-mode compile.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import CSRMatrix, compile_sparse_attention, sparse_attention
from repro.core.jit_cache import JitCache
from repro.core.plan import STRATEGIES

N_DEV = len(jax.devices())
# the fused backend with every block-row tagged VPU, and as the mask
# tags it; sampled by key
FUSED = {"vpu": dict(backend="pallas_bcsr", mxu_gain=0.0),
         "pallas_bcsr": dict(backend="pallas_bcsr")}


def _mask_from_lengths(lengths, n, seed):
    """Deterministic weighted mask with given per-row nnz (capped)."""
    rng = np.random.default_rng(seed)
    lengths = np.minimum(np.asarray(lengths, np.int64), n)
    row_ptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    cols = np.concatenate(
        [np.sort(rng.choice(n, size=int(ln), replace=False))
         for ln in lengths] or [np.zeros(0, np.int64)]).astype(np.int32)
    vals = rng.uniform(0.1, 2.0, int(row_ptr[-1])).astype(np.float32)
    return CSRMatrix((len(lengths), n), row_ptr, cols, jnp.asarray(vals))


@st.composite
def mask_cases(draw):
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
    return _mask_from_lengths(lengths, n, seed)


def _dense_oracle(a, vals, q, k, v):
    m, n = a.shape
    rows = np.repeat(np.arange(m), np.diff(a.row_ptr))
    W = np.zeros((m, n), np.float64)
    W[rows, a.col_indices] = np.asarray(vals, np.float64)
    scale = q.shape[1] ** -0.5
    z = (np.asarray(q, np.float64) @ np.asarray(k, np.float64).T) * scale
    zm = np.where(W > 0, z, -np.inf)
    zmax = np.max(zm, axis=1, initial=-np.inf)
    zmax = np.where(np.isfinite(zmax), zmax, 0.0)
    zc = np.where(W > 0, z, zmax[:, None])
    p = W * np.exp(zc - zmax[:, None])
    denom = p.sum(axis=1)
    return (p @ np.asarray(v, np.float64)) \
        / np.where(denom > 0, denom, 1.0)[:, None]


def _qkv(a, dh, dv, seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((a.m, dh)), jnp.float32),
            jnp.asarray(rng.standard_normal((a.n, dh)), jnp.float32),
            jnp.asarray(rng.standard_normal((a.n, dv)), jnp.float32))


@settings(max_examples=10, deadline=None)
@given(a=mask_cases(), dh=st.integers(1, 16), dv=st.integers(1, 24),
       strategy=st.sampled_from(STRATEGIES),
       backend=st.sampled_from(sorted(FUSED)))
def test_fused_sandwich_matches_dense_oracle(a, dh, dv, strategy,
                                             backend):
    q, k, v = _qkv(a, dh, dv, seed=dh + dv)
    y = sparse_attention(a, q, k, v, strategy=strategy, **FUSED[backend],
                         interpret=True, cache=JitCache())
    np.testing.assert_allclose(np.asarray(y),
                               _dense_oracle(a, a.vals, q, k, v),
                               rtol=1e-4, atol=1e-5)


@settings(max_examples=8, deadline=None)
@given(a=mask_cases(), dh=st.integers(1, 12),
       strategy=st.sampled_from(STRATEGIES),
       backend=st.sampled_from(sorted(FUSED)))
def test_gradient_matches_ref_backend(a, dh, strategy, backend):
    q, k, v = _qkv(a, dh, dh, seed=dh + 1)
    vals = jnp.asarray(a.vals)

    def grad_of(c):
        def f(w, qq, kk, vv):
            return jnp.sum(jnp.sin(c(w, qq, kk, vv)))
        return jax.grad(f, argnums=(0, 1, 2, 3))(vals, q, k, v)

    gf = grad_of(compile_sparse_attention(
        a, dh, strategy=strategy, **FUSED[backend], interpret=True,
        cache=JitCache()))
    gr = grad_of(compile_sparse_attention(
        a, dh, strategy=strategy, backend="ref", cache=JitCache()))
    for x, y, name in zip(gf, gr, ("vals", "q", "k", "v")):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


@settings(max_examples=8, deadline=None)
@given(a=mask_cases(), dh=st.integers(1, 12),
       strategy=st.sampled_from(STRATEGIES),
       backend=st.sampled_from(sorted(FUSED)),
       staging=st.sampled_from(("resident", "dma")),
       chips=st.integers(1, 4))
def test_staged_sharded_bit_matches_resident_single(a, dh, strategy,
                                                    backend, staging,
                                                    chips):
    chips = min(chips, N_DEV)
    q, k, v = _qkv(a, dh, dh, seed=dh + 2)
    y0 = sparse_attention(a, q, k, v, strategy=strategy,
                          **FUSED[backend], interpret=True,
                          staging="resident", cache=JitCache())
    y = sparse_attention(a, q, k, v, strategy=strategy, **FUSED[backend],
                         interpret=True, staging=staging, n_chips=chips,
                         cache=JitCache())
    assert np.array_equal(np.asarray(y0), np.asarray(y))
