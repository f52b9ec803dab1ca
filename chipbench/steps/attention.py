"""One layer's sparse self-attention through the program's public
entry: ``compile_sparse_attention(mask, head_dim)`` and then one
artifact call per head, as ``models/sparse_attention.py`` unrolls them.

Operands are made on the device in one jitted call from the seed: Q, K
and V standard normal, (heads, S, head_dim) float32, split per head at
set-up.  The mask weights are 1.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def operands(key, heads: int, S: int, dh: int, nnz: int) -> dict:
    kq, kk, kv = jax.random.split(key, 3)
    shape = (heads, S, dh)
    return {"q": jax.random.normal(kq, shape, jnp.float32),
            "k": jax.random.normal(kk, shape, jnp.float32),
            "v": jax.random.normal(kv, shape, jnp.float32),
            "vals": jnp.ones((nnz,), jnp.float32)}


@dataclasses.dataclass
class Step:
    call: object        # () -> {"out": [per-head (S, dh) arrays]}
    inputs: dict        # the operands, for the reference


def build(structure, config: dict, traffic: dict, key) -> Step:
    from repro.core import CSRMatrix, compile_sparse_attention
    row_ptr, cols, (S, _) = structure
    heads, dh = int(config["num_attention_heads"]), int(config["head_dim"])
    inputs = operands(key, heads, S, dh, int(cols.shape[0]))
    vals = inputs["vals"]
    mask = CSRMatrix((S, S), row_ptr, cols, vals)
    art = compile_sparse_attention(mask, dh)
    q, k, v = ([a[h] for h in range(heads)]
               for a in (inputs["q"], inputs["k"], inputs["v"]))

    def layer():
        outs = []
        for h in range(heads):
            with jax.profiler.TraceAnnotation(f"call_head_{h}"):
                outs.append(art(vals, q[h], k[h], v[h]))
        return {"out": outs}
    return Step(call=layer, inputs=inputs)
