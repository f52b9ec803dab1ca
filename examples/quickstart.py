"""Quickstart: JIT-specialized SpMM in 30 lines.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import (GLOBAL_CACHE, build_plan, compile_spmm, random_csr,
                        spmm)

# a skewed (power-law) sparse matrix — the case that motivates the
# paper's workload-division strategies
a = random_csr(1024, 1024, density=0.02, family="powerlaw", seed=0)
x = jnp.asarray(np.random.default_rng(1).standard_normal((1024, 45)),
                jnp.float32)
print(f"A: {a.shape}, nnz={a.nnz}, fingerprint={a.fingerprint[:12]}…")

# plan-time = the paper's JIT codegen time: inspect what each strategy does
for strategy in ("row_split", "nnz_split", "merge_split"):
    plan = build_plan(a.row_ptr, a.col_indices, a.shape, 45,
                      strategy=strategy)
    print(f"  {strategy:12s} -> {plan.stats()}")

# one-shot API (plans + compiles on first call; cached thereafter)
y = spmm(a, x, strategy="nnz_split", backend="ref")
print("Y:", y.shape, "matches dense:",
      bool(jnp.allclose(y, a.to_dense() @ x, atol=1e-3)))

# Pallas TPU kernels, validated on CPU via interpret mode
y_pl = spmm(a, x, strategy="nnz_split", backend="pallas_bcsr",
            interpret=True)
print("pallas_bcsr matches:", bool(jnp.allclose(y_pl, y, atol=1e-3)))

# the jit-function cache (paper Table IV): second call is a pure hit
compiled = compile_spmm(a, 45, strategy="nnz_split", backend="ref")
print("cache:", GLOBAL_CACHE.stats())
