"""The SpMM step through the program's public entry: ``compile_spmm(a,
d)`` and then the artifact's ``__call__(vals, x)``, called eagerly as
users call it.  With ``grad`` the step is one forward and backward:
``jax.vjp`` of the artifact at (vals, X), pulled back through dY, which
runs the forward, the transposed SpMM and the chunked SDDMM.

Operands are made on the device in one jitted call from the seed: A's
values uniform in [0.5, 1.5), X and dY standard normal, float32.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def operands(key, nnz: int, m: int, n: int, d: int, grad: bool) -> dict:
    kv, kx, kdy = jax.random.split(key, 3)
    out = {"vals": jax.random.uniform(kv, (nnz,), jnp.float32, 0.5, 1.5),
           "x": jax.random.normal(kx, (n, d), jnp.float32)}
    if grad:
        out["dy"] = jax.random.normal(kdy, (m, d), jnp.float32)
    return out


@dataclasses.dataclass
class Step:
    call: object        # () -> {output name: array}
    inputs: dict        # the operands, for the reference


def build(structure, config: dict, traffic: dict, key) -> Step:
    from repro.core import CSRMatrix, compile_spmm
    row_ptr, cols, (m, n) = structure
    d = int(config["width"])
    grad = bool(traffic.get("grad"))
    inputs = operands(key, int(cols.shape[0]), m, n, d, grad)
    a = CSRMatrix((m, n), row_ptr, cols, inputs["vals"])
    art = compile_spmm(a, d)
    vals, x = inputs["vals"], inputs["x"]
    if not grad:
        return Step(call=lambda: {"y": art(vals, x)}, inputs=inputs)
    dy = inputs["dy"]

    def train():
        y, pull = jax.vjp(art, vals, x)
        dvals, dx = pull(dy)
        return {"y": y, "dvals": dvals, "dx": dx}
    return Step(call=train, inputs=inputs)
