"""Plain jnp reference of one layer's sparse self-attention,
independent of the program: per head, scores ``z = (Q[row] * scale) .
K[col]`` on the mask's entries, ``p = w exp(z - max_row z)``, and
``O[row] = sum p V[col] / sum p``, one head at a time.

``precision="control"`` computes both products from bfloat16-rounded
operands (one MXU pass, as at ``Precision.DEFAULT``): the nearest step
below the float32 that the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _mul_bf16(a, b):
    def rnd(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return rnd(a) * rnd(b)


PRODUCTS = {"reference": jnp.multiply, "control": _mul_bf16}


@functools.partial(jax.jit, static_argnames=("S", "precision"))
def _head(rows, cols, w, q, k, v, scale, S: int, precision: str):
    mul = PRODUCTS[precision]
    z = jnp.sum(mul((q * scale)[rows], k[cols]), axis=-1)
    zmax = jax.ops.segment_max(z, rows, num_segments=S,
                               indices_are_sorted=True)
    p = w * jnp.exp(z - zmax[rows])
    den = jax.ops.segment_sum(p, rows, num_segments=S,
                              indices_are_sorted=True)
    num = jax.ops.segment_sum(mul(p[:, None], v[cols]), rows,
                              num_segments=S, indices_are_sorted=True)
    return num / den[:, None]


def compute(structure, config: dict, traffic: dict, inputs: dict,
            precision: str) -> dict:
    if precision not in PRODUCTS:
        raise ValueError(precision)
    row_ptr, cols, (S, _) = structure
    rows = jnp.asarray(np.repeat(np.arange(S, dtype=np.int32),
                                 np.diff(row_ptr)))
    cols = jnp.asarray(cols.astype(np.int32))
    scale = float(config["head_dim"]) ** -0.5
    q, k, v, w = (inputs[name] for name in ("q", "k", "v", "vals"))
    out = [_head(rows, cols, w, q[h], k[h], v[h], scale, S=S,
                 precision=precision)
           for h in range(q.shape[0])]
    return {"out": jnp.stack(out)}
