"""Plain jnp reference of the SpMM step, independent of the program:
``Y = A X`` as a scatter-add of ``vals[e] * X[col[e]]`` into row
``row[e]``; for training also ``dvals[e] = <dY[row[e]], X[col[e]]>`` and
``dX = A^T dY`` (the same scatter-add with rows and columns swapped).
Nonzeros are taken in chunks so the gathered rows fit beside the cell.

``precision="control"`` is the same reference fed bfloat16-rounded
operands: the nearest step below the configuration's float32 products
and sums, which no correct run may be mistaken for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 21


def _chunked(a: np.ndarray, fill) -> jax.Array:
    pad = -a.shape[0] % CHUNK
    return jnp.asarray(np.pad(a, (0, pad), constant_values=fill)
                       .reshape(-1, CHUNK))


@functools.partial(jax.jit, static_argnames=("num", "in_order"))
def _scatter_rows(seg, src, w, x, num: int, in_order: bool):
    def body(i, y):
        return y.at[seg[i]].add(w[i][:, None] * x[src[i]],
                                indices_are_sorted=in_order)
    return jax.lax.fori_loop(0, seg.shape[0], body,
                             jnp.zeros((num, x.shape[1]), jnp.float32))


@jax.jit
def _sddmm(rows, cols, dy, x):
    def body(i, out):
        return out.at[i].set(jnp.sum(dy[rows[i]] * x[cols[i]], axis=-1))
    return jax.lax.fori_loop(0, rows.shape[0], body,
                             jnp.zeros(rows.shape, jnp.float32)).reshape(-1)


def compute(structure, config: dict, traffic: dict, inputs: dict,
            precision: str) -> dict:
    row_ptr, cols, (m, n) = structure
    nnz = int(cols.shape[0])
    rows = np.repeat(np.arange(m, dtype=np.int32), np.diff(row_ptr))
    rows_c, cols_c = _chunked(rows, 0), _chunked(cols.astype(np.int32), 0)
    ops = {k: v for k, v in inputs.items()}
    if precision == "control":
        ops = {k: jax.lax.reduce_precision(v, exponent_bits=8,
                                           mantissa_bits=7)
               for k, v in ops.items()}
    elif precision != "reference":
        raise ValueError(precision)
    vals = jnp.pad(ops["vals"], (0, -nnz % CHUNK)).reshape(-1, CHUNK)
    out = {"y": _scatter_rows(rows_c, cols_c, vals, ops["x"], num=m,
                              in_order=True)}
    if traffic.get("grad"):
        out["dvals"] = _sddmm(rows_c, cols_c, ops["dy"], ops["x"])[:nnz]
        out["dx"] = _scatter_rows(cols_c, rows_c, vals, ops["dy"], num=n,
                                  in_order=False)
    return out
