"""Paper Fig. 9/10 analogue: the three workload-division strategies
across matrix families and d in {16, 32}.

Reported per cell: wall time, plan padding efficiency (the balance
metric the strategies compete on), and speedup vs the AOT dense
baseline.  The skewed (powerlaw) family is where nnz/merge-split beat
row-split — the paper's motivating case.

A second sweep times the fused all-VPU plan (``mxu_gain=0``; interpret
mode, so a smaller matrix) and reports the Table IV dispatch invariant:
one pallas_call per instance, whatever the plan's segment count — the
single-segment row_split cell is the no-regression baseline the fused
refactor is held to.

A third sweep (``--n-chips C``, or ``run(n_chips=C)``) shards the fused
plan over a 1-D device mesh: for each chip count up to C it reports wall
time, the cross-chip padding efficiency, and launches per call (== chip
count under shard_map).  Force a CPU device mesh with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

try:
    from .common import bench_record, csv_row, time_fn
except ImportError:          # plain-script run: python benchmarks/...
    import pathlib
    import sys
    _ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(_ROOT / "src"))   # repro package
    sys.path.insert(0, str(_ROOT))           # benchmarks package
    from benchmarks.common import bench_record, csv_row, time_fn

from repro.core import (TuneConfig, autotune_spmm_with_result, build_plan,
                        build_workspace, compile_spmm, random_csr)
from repro.core.jit_cache import JitCache
from repro.kernels import ops


def _chip_sweep(max_chips: int) -> list:
    rows = []
    avail = len(jax.devices())
    rng = np.random.default_rng(5)
    a = random_csr(512, 512, density=0.02, family="powerlaw", seed=11)
    x = jnp.asarray(rng.standard_normal((512, 16)), jnp.float32)
    vals = jnp.asarray(a.vals)
    chips = 1
    sweep = []
    while chips <= max_chips:
        sweep.append(chips)
        chips *= 2
    if sweep[-1] != max_chips:
        sweep.append(max_chips)
    for n_chips in sweep:
        if n_chips > avail:
            rows.append(csv_row(f"sharded_ell_c{n_chips}_m512_d16", 0.0,
                                f"SKIPPED:only_{avail}_devices"))
            continue
        c = compile_spmm(a, 16, strategy="nnz_split", backend="pallas_bcsr",
                         mxu_gain=0.0, interpret=True, n_chips=n_chips,
                         cache=JitCache())
        ops.reset_dispatch_counts()
        warmup, iters = 1, 3
        us = time_fn(c, vals, x, warmup=warmup, iters=iters)
        calls = warmup + iters
        eff = c.sharded_workspace.efficiency
        rows.append(csv_row(
            f"sharded_ell_c{n_chips}_m512_d16", us,
            f"efficiency={eff:.3f};"
            f"launches_per_call="
            f"{ops.DISPATCH_COUNTS['bcsr_fused'] / calls:.0f}"))
    return rows


def run(n_chips: int = 0) -> list:
    rows = []
    rng = np.random.default_rng(2)
    for family in ("uniform", "powerlaw", "banded"):
        a = random_csr(4096, 4096, density=0.004, family=family, seed=7)
        for d in (16, 32):
            x = jnp.asarray(rng.standard_normal((4096, d)), jnp.float32)
            dense_a = a.to_dense()
            us_dense = time_fn(jax.jit(lambda A, X: A @ X), dense_a, x)
            for strategy in ("row_split", "nnz_split", "merge_split"):
                plan = build_plan(a.row_ptr, a.col_indices, a.shape, d,
                                  strategy=strategy)
                c = compile_spmm(a, d, strategy=strategy, backend="ref",
                                 cache=JitCache())
                vals = jnp.asarray(a.vals)
                us = time_fn(jax.jit(lambda v, X: c(v, X)), vals, x)
                rows.append(csv_row(
                    f"fig9_{strategy}_{family}_d{d}", us,
                    f"efficiency={plan.efficiency:.3f};"
                    f"segments={len(plan.segments)};"
                    f"speedup_vs_dense={us_dense/us:.2f}x"))

    # fused all-VPU dispatch sweep (interpret mode => small instance)
    a = random_csr(256, 256, density=0.03, family="powerlaw", seed=7)
    x = jnp.asarray(rng.standard_normal((256, 16)), jnp.float32)
    vals = jnp.asarray(a.vals)
    for strategy in ("row_split", "nnz_split", "merge_split"):
        c = compile_spmm(a, 16, strategy=strategy, backend="pallas_bcsr",
                         mxu_gain=0.0, interpret=True, cache=JitCache())
        ops.reset_dispatch_counts()
        warmup, iters = 1, 3
        us = time_fn(c, vals, x, warmup=warmup, iters=iters)
        calls = warmup + iters
        rows.append(csv_row(
            f"fused_ell_{strategy}_m256_d16", us,
            f"segments={len(c.mixed_plan.vpu.segments)};"
            f"launches_per_call="
            f"{ops.DISPATCH_COUNTS['bcsr_fused'] / calls:.0f}"))

    if n_chips > 0:
        rows += _chip_sweep(n_chips)
    return rows


def _timed_cell(bench, strategy, backend, n_chips, a, x, *, counter,
                extra=(), staging=None, x_sharding=None,
                merge_threshold=0):
    """One smoke cell: compile, time, count launches per call."""
    kw = dict(strategy=strategy, backend=backend, interpret=True,
              cache=JitCache())
    if n_chips:
        kw["n_chips"] = n_chips
    if staging:
        kw["staging"] = staging
    if x_sharding:
        kw["x_sharding"] = x_sharding
    if merge_threshold:
        kw["merge_threshold"] = merge_threshold
    c = compile_spmm(a, x.shape[1], **kw)
    vals = jnp.asarray(a.vals)
    ops.reset_dispatch_counts()
    # min-of-7: the smoke gate compares at a 2x threshold, and the min
    # filters the scheduler/GC spikes a median of interpret-mode cells
    # still lets through (see time_fn)
    warmup, iters = 2, 7
    us = time_fn(c, vals, x, warmup=warmup, iters=iters, stat="min")
    calls = warmup + iters
    dispatches = sum(ops.DISPATCH_COUNTS[k]
                     for k in (counter, *extra)) / calls
    return bench_record(bench, strategy, backend, n_chips, us / 1e3,
                        dispatches)


def _tuned_suite(bench, backend, a, x, *, counter):
    """The autotuned smoke cell (DESIGN.md §11): every candidate of the
    strategy × merge-threshold grid is MEASURED with the identical
    min-of-7 timer.  The tuned record's strategy field is pinned to
    "auto": the winner's identity may legitimately drift run to run,
    the record key must not."""
    cands = [TuneConfig(strategy=s, merge_threshold=t)
             for s in ("row_split", "nnz_split", "merge_split")
             for t in (0, 16)]

    def measure(c, vals, xx):
        return time_fn(c, vals, xx, warmup=2, iters=7, stat="min") / 1e6

    cache = JitCache()
    c, res = autotune_spmm_with_result(
        a, x.shape[1], backend=backend, interpret=True, candidates=cands,
        top_k=len(cands), measure=measure, cache=cache)
    ops.reset_dispatch_counts()
    jax.block_until_ready(c(jnp.asarray(a.vals), x))
    return [bench_record(bench, "auto", backend, 0,
                         res.best_measured_s * 1e3,
                         ops.DISPATCH_COUNTS[counter])]


def smoke_records() -> list:
    """CI bench-smoke cells (schema: benchmarks/common.py): the fused
    mixed VPU/MXU hot path, unsharded + sharded, on fixtures
    small enough for interpret-mode CPU.  Tracks the two regression
    axes that matter for the hot path: wall-clock per call and
    pallas_call launches per call (the Table IV fusion invariant)."""
    records = []
    rng = np.random.default_rng(2)
    a = random_csr(128, 128, density=0.05, family="powerlaw", seed=7)
    x = jnp.asarray(rng.standard_normal((128, 16)), jnp.float32)
    # the sharded cells are PINNED to 1 chip: n_chips is part of the
    # bench-record key, so a host-dependent count would make the gate
    # compare different cells on different machines (baseline poisoning
    # / phantom coverage failures).  1 chip still exercises the whole
    # shard_map dispatch path; real multi-chip behavior is covered by
    # the mesh8 pytest leg, not the bench trajectory.
    for strategy in ("row_split", "nnz_split", "merge_split"):
        records.append(_timed_cell("fused_mixed", strategy, "pallas_bcsr",
                                   0, a, x, counter="bcsr_fused"))
    records.append(_timed_cell("fused_mixed_sharded", "nnz_split",
                               "pallas_bcsr", 1, a, x,
                               counter="bcsr_fused"))
    # staged (DMA) cells: the "_dma" bench-name suffix is the staging
    # axis (the record key has no staging field — see the schema note in
    # benchmarks/common.py).  Interpret-mode DMA is EMULATED, so these
    # wall cells track the emulation's plumbing cost, not TPU overlap;
    # the dispatch counts pin the fusion invariant on the staged path.
    for strategy in ("row_split", "nnz_split", "merge_split"):
        records.append(_timed_cell("fused_mixed_dma", strategy,
                                   "pallas_bcsr", 0, a, x,
                                   counter="bcsr_fused", staging="dma"))
    records.append(_timed_cell("fused_mixed_dma_sharded", "nnz_split",
                               "pallas_bcsr", 1, a, x,
                               counter="bcsr_fused", staging="dma"))
    # X-sharded cells: the "_xshard" bench-name suffix is the X-placement
    # axis (x_sharding="rows" — fetch-table exchange + remapped column
    # streams), pinned to 1 chip like the other sharded cells so record
    # keys never depend on visible devices.  1 chip still exercises the
    # whole exchange path (all_to_all, strip packing, remap); the wall
    # cell tracks its plumbing cost, the dispatch count pins the
    # one-call-per-chip invariant on the x-sharded lowering.
    records.append(_timed_cell("fused_mixed_xshard", "nnz_split",
                               "pallas_bcsr", 1, a, x,
                               counter="bcsr_fused", x_sharding="rows"))
    records.append(_timed_cell("fused_mixed_dma_xshard", "nnz_split",
                               "pallas_bcsr", 1, a, x,
                               counter="bcsr_fused", staging="dma",
                               x_sharding="rows"))
    # CGCM-merged cells (DESIGN.md §7.9): the "_merged" bench-name
    # suffix is the merge axis (merge_threshold=16 vs the default 0).
    # Structurally the merged powerlaw plan MUST run strictly fewer
    # grid steps — assert it here so the bench can never silently
    # report a merged cell that didn't merge.
    ws0 = build_workspace(a.row_ptr, a.col_indices, a.shape, 16,
                          merge_threshold=0)
    ws1 = build_workspace(a.row_ptr, a.col_indices, a.shape, 16,
                          merge_threshold=16)
    assert ws1.num_trips < ws0.num_blocks, \
        "CGCM must shrink the powerlaw grid (merge stage inert?)"
    records.append(_timed_cell("fused_mixed_merged", "nnz_split",
                               "pallas_bcsr", 0, a, x,
                               counter="bcsr_fused", merge_threshold=16))
    # the autotuned cell (DESIGN.md §11)
    records += _tuned_suite("fused_mixed_tuned", "pallas_bcsr", a, x,
                            counter="bcsr_fused")
    return records


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-chips", type=int, default=0,
                    help="also sweep the sharded fused path up to this "
                         "many chips (needs a multi-device mesh, e.g. "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in run(n_chips=args.n_chips):
        print(row, flush=True)
