"""MoE <-> SpMM integration: the in-jit gather path must agree with the
concrete-routing JIT-planned SpMM paths on identical routings (the
first-class integration of the paper's technique, DESIGN.md §4.4)."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import moe_spmm as ms


def _setup(T=24, D=16, E=4, k=2, C=12, F=32, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((T, D)), jnp.float32),
            jnp.asarray(rng.standard_normal((T, E)), jnp.float32),
            jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32),
            jnp.asarray(rng.standard_normal((E, F, D)) * 0.1, jnp.float32))


def _gather_path(tokens, logits, w_up, w_dn, k, C):
    E = w_up.shape[0]
    gates, eids, slots = ms.topk_routing(logits, k, C)
    xe = ms.dispatch(tokens, eids, slots, E, C)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_up))
    oe = jnp.einsum("ecf,efd->ecd", h, w_dn)
    return ms.combine(oe, gates, eids, slots)


@pytest.mark.parametrize("backend", ["ref", "vpu", "pallas_bcsr"])
def test_moe_gather_equals_concrete_spmm(backend, monkeypatch):
    if backend == "vpu":
        # the fused backend with every block-row tagged VPU
        spmm_mod = importlib.import_module("repro.core.spmm")
        monkeypatch.setattr(spmm_mod, "spmm", functools.partial(
            spmm_mod.spmm, mxu_gain=0.0))
        backend = "pallas_bcsr"
    tokens, logits, w_up, w_dn = _setup()
    y_gather = _gather_path(tokens, logits, w_up, w_dn, 2, 12)
    y_spmm = ms.moe_apply_concrete(tokens, logits, w_up, w_dn, top_k=2,
                                   capacity=12, backend=backend,
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(y_gather), np.asarray(y_spmm),
                               rtol=1e-4, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 100), k=st.integers(1, 3),
       C=st.integers(2, 16))
def test_moe_consistency_property(seed, k, C):
    tokens, logits, w_up, w_dn = _setup(seed=seed)
    y1 = _gather_path(tokens, logits, w_up, w_dn, k, C)
    y2 = ms.moe_apply_concrete(tokens, logits, w_up, w_dn, top_k=k,
                               capacity=C, backend="ref")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-5)


def test_routing_csr_row_nnz_at_most_topk():
    _, logits, _, _ = _setup()
    gates, eids, slots = ms.topk_routing(logits, 2, 3)   # tight capacity
    s = ms.routing_to_csr(gates, eids, slots, 4, 3)
    assert s.shape == (24, 12)
    assert np.all(s.row_lengths <= 2)                    # <= top_k (drops)
    assert s.nnz <= 24 * 2
    # capacity respected per expert-slot column: each column used once
    cols, counts = np.unique(s.col_indices, return_counts=True)
    assert np.all(counts == 1)


def test_capacity_overflow_drops_deterministically():
    # all tokens prefer expert 0: capacity forces drops
    T, E, k, C = 16, 4, 1, 4
    logits = jnp.zeros((T, E)).at[:, 0].set(10.0)
    gates, eids, slots = ms.topk_routing(logits, k, C)
    kept = int(jnp.sum(slots < C))
    assert kept == C                      # first C tokens keep their slot
    assert np.all(np.asarray(eids[:, 0]) == 0)


def test_routing_matrix_values_are_gates():
    tokens, logits, w_up, w_dn = _setup()
    gates, eids, slots = ms.topk_routing(logits, 2, 12)
    s = ms.routing_to_csr(gates, eids, slots, 4, 12)
    np.testing.assert_allclose(float(jnp.sum(s.vals)),
                               float(jnp.sum(jnp.where(slots < 12, gates,
                                                       0.0))), rtol=1e-5)
