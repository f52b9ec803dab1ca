"""Paper Table IV analogue: codegen (plan+lower) overhead vs execution.

The paper reports JIT codegen at 0.0003%-0.02% of execution time.  Our
"codegen" = host-side planning (workload division + ELL packing + CCM
tiling + fused-workspace/descriptor-table packing) + first-call jit
lowering; both amortize across the cache.  ``ws_ms`` isolates the
descriptor-table packing cost the fused dispatch added — it must stay
plan-sized (one pass over padded slots), not execution-sized.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compile_spmm, random_csr
from repro.core.jit_cache import JitCache
from repro.core.plan import build_fused_workspace, build_mixed_plan
from repro.kernels import ops

from .common import bench_record, csv_row, time_fn


def run() -> list:
    rows = []
    rng = np.random.default_rng(1)
    for family, m, density, calls in [("powerlaw", 4096, 0.01, 100),
                                      ("uniform", 2048, 0.02, 100)]:
        a = random_csr(m, m, density=density, family=family, seed=3)
        x = jnp.asarray(rng.standard_normal((m, 16)), jnp.float32)
        cache = JitCache()
        t0 = time.perf_counter()
        c = compile_spmm(a, 16, backend="ref", cache=cache)
        plan_s = time.perf_counter() - t0          # the "codegen" step
        vals = jnp.asarray(a.vals)
        f = jax.jit(lambda v, X: c(v, X))
        us = time_fn(f, vals, x, iters=20)
        exec_total_s = us * 1e-6 * calls
        overhead_pct = 100.0 * plan_s / (plan_s + exec_total_s)
        # cache-hit path: re-"compile" must be ~free
        t1 = time.perf_counter()
        compile_spmm(a, 16, backend="ref", cache=cache)
        hit_us = (time.perf_counter() - t1) * 1e6
        # descriptor-table packing cost of the fused all-VPU plan
        plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16,
                                mxu_gain=0.0)
        t2 = time.perf_counter()
        build_fused_workspace(plan)
        ws_ms = (time.perf_counter() - t2) * 1e3
        rows.append(csv_row(
            f"table4_codegen_{family}_m{m}", us,
            f"plan_ms={plan_s*1e3:.2f};ws_ms={ws_ms:.2f};"
            f"overhead_pct_at_{calls}calls="
            f"{overhead_pct:.4f};cache_hit_us={hit_us:.1f}"))
    return rows


def smoke_records() -> list:
    """CI bench-smoke cells for the "codegen" (plan + pack) side: the
    host-side cost of building a plan and its fused descriptor tables
    must stay plan-sized.  ``dispatches`` is 0 — these cells gate on
    wall-clock only (see benchmarks/common.py for the schema)."""
    def med_ms(fn, iters=5):
        # min-of-5: plan builds are ms-scale and the 2x regression gate
        # must not trip on scheduler noise (same rationale as time_fn's
        # stat="min" for the kernel smoke cells)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            ts.append((time.perf_counter() - t0) * 1e3)
        return float(np.min(ts))

    records = []
    a = random_csr(512, 512, density=0.02, family="powerlaw", seed=3)
    for strategy in ("row_split", "nnz_split", "merge_split"):
        mixed_ms = med_ms(lambda: build_fused_workspace(build_mixed_plan(
            a.row_ptr, a.col_indices, a.shape, 16, strategy=strategy)))
        records.append(bench_record("codegen_plan", strategy,
                                    "pallas_bcsr", 0, mixed_ms, 0))
    # validate="off" (the production default on TPU) must contribute
    # EXACTLY zero host seconds of static verification to the dispatch
    # path (DESIGN.md §15)
    small = random_csr(256, 256, density=0.03, family="powerlaw", seed=3)
    ops.reset_dispatch_counts()
    compile_spmm(small, 16, backend="pallas_bcsr", interpret=True,
                 validate="off", cache=JitCache())
    assert ops.BUILD_SECONDS["verify"] == 0.0, \
        "validate='off' must never touch the verifier"
    return records
