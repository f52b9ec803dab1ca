"""Pure-jnp oracles for every kernel in this package.

These are the correctness ground truth for the Pallas kernels (swept in
tests/test_kernels.py) and the "spmm_ref" dispatch mode used inside the
model stack on CPU / in the 512-device dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def spmm_dense_ref(a_dense: jax.Array, x: jax.Array) -> jax.Array:
    """Y = A·X with A densified — the simplest oracle."""
    return a_dense.astype(jnp.float32) @ x.astype(jnp.float32)


def spmm_csr_ref(row_ptr, col_indices, vals, x, m: int) -> jax.Array:
    """Row-by-row CSR oracle (Algorithm 1 of the paper, vectorized over d
    via CCM — Algorithm 2).  Host-side structure, jnp compute."""
    row_ptr = np.asarray(row_ptr)
    rows = np.repeat(np.arange(m), np.diff(row_ptr))
    prod = vals[:, None].astype(jnp.float32) * x[col_indices].astype(jnp.float32)
    return jax.ops.segment_sum(prod, jnp.asarray(rows), num_segments=m)


def spmm_bcsr_ref(block_row_ptr, block_cols, block_vals, x, bm: int,
                  bk: int) -> jax.Array:
    """Block-CSR oracle: per-block (bm x bk)·(bk x d) matmuls."""
    n_brows = len(block_row_ptr) - 1
    d = x.shape[1]
    y = jnp.zeros((n_brows * bm, d), dtype=jnp.float32)
    block_row_ptr = np.asarray(block_row_ptr)
    block_cols = np.asarray(block_cols)
    for i in range(n_brows):
        acc = jnp.zeros((bm, d), dtype=jnp.float32)
        for p in range(int(block_row_ptr[i]), int(block_row_ptr[i + 1])):
            c = int(block_cols[p])
            acc = acc + block_vals[p].astype(jnp.float32) @ \
                x[c * bk:(c + 1) * bk].astype(jnp.float32)
        y = y.at[i * bm:(i + 1) * bm].set(acc)
    return y


def sddmm_ref(row_ptr, col_indices, dy, x) -> jax.Array:
    """Sampled dense-dense matmul: dA.vals[p] = <dY[row_p], X[col_p]> —
    the structure-restricted gradient of spmm w.r.t. vals."""
    row_ptr = np.asarray(row_ptr)
    m = len(row_ptr) - 1
    rows = np.repeat(np.arange(m), np.diff(row_ptr))
    return jnp.sum(dy[rows].astype(jnp.float32) *
                   x[col_indices].astype(jnp.float32), axis=-1)
