#!/usr/bin/env python3
"""Prove that the fused SpMM path runs on a TPU, through the public
entry points, at sizes users run.

    python chip_smoke.py             # one chip: spmm, serving, attention
    python chip_smoke.py --chips 4   # only the sharded path on four chips

Phases, all in this one process (a chip belongs to one process):

  spmm       ``compile_spmm`` with backend, staging and interpret left to
             the platform, on a 2^20 x 2^20 power-law graph (about 16.6 M
             nonzeros) at d=128: forward and ``jax.grad`` with respect to
             the values and X against the ``ref`` backend, and a second
             ``compile_spmm`` that must hit the JitCache.
  serving    ``SpmmServer`` driven through ``SpmmScheduler``: 4 tenants
             of 2^16-row matrices, d in {64, 128}; every response
             against ``ref``.
  attention  ``compile_sparse_attention`` with longformer-1.4b's mask
             (window 512, 64 global columns, head_dim 128) at S=4096,
             one (Q, K, V) head against ``ref``; then the MXU panel each
             attention cell of the chip benchmark resolves to (Mellum2's
             causal window of 1,024 at head_dim 128, Longformer-large's
             two-sided window of 512 with 64 global tokens at 64), with
             its panels per call and their fill.
  --chips 4  sharded ``pallas_bcsr`` over a 4-chip ``("chips",)`` mesh
             on the 2^20 graph against ``ref`` and against the one-chip
             fused output; the per-chip tables must sit on four devices.

Inputs are generated from ``--seed``.  Errors are row-scaled: for each
output row, max |fused - ref| / (1 + max |ref|).  Times are smoke
times of one run, not a benchmark.  Any failed check exits non-zero;
the last line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

TOL = 1e-4          # row-scaled error bound against the ref backend


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def row_scaled_error(y, y_ref) -> float:
    y, y_ref = np.asarray(y, np.float64), np.asarray(y_ref, np.float64)
    if not np.all(np.isfinite(y)):
        return float("inf")
    scale = 1.0 + np.abs(y_ref).max(axis=-1, keepdims=True)
    return float((np.abs(y - y_ref) / scale).max(initial=0.0))


def check(phase: str, name: str, err: float, tol: float = TOL) -> None:
    log(phase, f"{name}: max row-scaled error {err:.3e} (tolerance "
               f"{tol:.0e})")
    if not err <= tol:
        raise AssertionError(f"{phase}: {name} error {err} > {tol}")


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def row_chunks(a, n_chunks: int):
    """The CSR row blocks of ``a`` as standalone matrices, so the ref
    backend's (nnz, d) intermediates stay a fraction of HBM."""
    from repro.core import CSRMatrix
    bounds = np.linspace(0, a.m, n_chunks + 1).astype(np.int64)
    vals = np.asarray(a.vals)
    for r0, r1 in zip(bounds[:-1], bounds[1:]):
        s, e = int(a.row_ptr[r0]), int(a.row_ptr[r1])
        yield r0, r1, s, e, CSRMatrix(
            (int(r1 - r0), a.n), a.row_ptr[r0:r1 + 1] - s,
            a.col_indices[s:e], vals[s:e])


def ref_forward_and_grads(a, x, dy, n_chunks: int = 8):
    """The ref backend's forward and its gradients of <A·X, dy> with
    respect to the values and X, assembled chunk by chunk."""
    import jax
    import jax.numpy as jnp
    from repro.core import JitCache, compile_spmm
    cache = JitCache()
    ys, dvals, dx = [], [], jnp.zeros_like(x)
    for r0, r1, _, _, chunk in row_chunks(a, n_chunks):
        c = compile_spmm(chunk, x.shape[1], backend="ref", cache=cache)

        @jax.jit
        def fwd_vjp(v, xx, g):
            y, vjp = jax.vjp(c, v, xx)
            return (y, *vjp(g))

        y, dv, dxc = fwd_vjp(jnp.asarray(chunk.vals), x, dy[r0:r1])
        ys.append(np.asarray(y))
        dvals.append(np.asarray(dv))
        dx = dx + dxc
    return np.concatenate(ys), np.concatenate(dvals), np.asarray(dx)


def power_law_graph(seed: int, log2_n: int = 20):
    from repro.core import random_csr
    n = 1 << log2_n
    return random_csr(n, n, density=16 / n, family="powerlaw", seed=seed)


def describe_fused(c) -> str:
    """One line on a one-chip fused artifact: how it resolved, its
    trips and window, the slot-value stream's slots beside the
    nonzeros, the loop steps of its VPU trips, the elements its staging
    gathers, its descriptors and piece trips."""
    fw = c._fused
    tags = np.asarray(fw.blk_tag)[np.asarray(fw.blk_L) > 0]
    return (f"backend={c.backend} staging={c.staging} "
            f"interpret={c.interpret} trips={fw.num_blocks} "
            f"window={fw.max_span} nnz={c._nnz} slots={c.vals_slots} "
            f"vpu_steps={c.vpu_steps} "
            f"gathered={c.vals_gather_elems}; VPU/MXU descriptors "
            f"{int((tags == 0).sum())} / {int((tags == 1).sum())}; "
            f"piece trips {int(np.asarray(fw.cont).sum())}")


def describe_panel(c) -> str:
    """The MXU panel an attention artifact resolved to, the panels one
    call multiplies, their fill (nonzeros over slots), and whether its
    descriptor stream holds VPU trips."""
    return (f"panel {c.bm}x{c.bk}: {c.mxu_panels} MXU panels per call, "
            f"fill {c.panel_fill:.4f}, VPU trips {c._vpu}")


def benchmark_attention_masks():
    """The attention cells' masks with their head widths: Mellum2's
    through the program's own mask builder, Longformer's through the
    benchmark's generator (loaded by path)."""
    import importlib.util
    import jax.numpy as jnp
    from repro.core import CSRMatrix
    from repro.models.sparse_attention import sparse_attention_mask
    path = (Path(__file__).resolve().parent / "chipbench" / "gen"
            / "longformer_mask.py")
    spec = importlib.util.spec_from_file_location("longformer_mask", path)
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)
    rp, cols, shape = gen.generate({"seq_len": 4096,
                                    "attention_window": 512,
                                    "global_tokens": 64})
    longformer = CSRMatrix(shape, rp, cols,
                           jnp.ones((cols.size,), jnp.float32))
    return {"mellum2.prefill_4k": (sparse_attention_mask(4096, 1024, 0),
                                   128),
            "longformer.attn_fwd": (longformer, 64)}


def slot_table_devices(c) -> list:
    """For each per-chip slot-value table of a sharded artifact (the
    element-gathered indices and, on a plan with MXU panels, the live
    lanes), the number of devices it sits on."""
    vals = c._sharded.vals
    return [len({s.device for s in t.addressable_shards})
            for t in (vals.gather, vals.lanes) if t is not None]


def phase_spmm(seed: int):
    import jax
    import jax.numpy as jnp
    from repro.core import GLOBAL_CACHE, compile_spmm
    from repro.kernels import ops

    a, t_gen = timed(lambda: power_law_graph(seed))
    log("spmm", f"graph {a.m} x {a.n}, nnz {a.nnz}, generated in "
                f"{t_gen:.2f}s")
    d = 128
    kx, kdy = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (a.n, d), jnp.float32)
    dy = jax.random.normal(kdy, (a.m, d), jnp.float32)
    vals = jnp.asarray(a.vals)

    c, t_plan = timed(lambda: compile_spmm(a, d))
    log("spmm", f"compile_spmm (plan + pack + device tables) {t_plan:.2f}s:"
                f" {describe_fused(c)}")
    if (c.backend, c.staging, c.interpret) != ("pallas_bcsr", "dma", False):
        raise AssertionError(f"main path resolved to {c.backend}/"
                             f"{c.staging}/interpret={c.interpret}")

    ops.reset_dispatch_counts()
    y, t_first = timed(lambda: c(vals, x).block_until_ready())
    counts = dict(ops.DISPATCH_COUNTS)
    _, t_warm = timed(lambda: c(vals, x).block_until_ready())
    log("spmm", f"forward first call {t_first:.2f}s (compile included), "
                f"warm call {t_warm:.4f}s; dispatches {counts}")
    if counts.get("bcsr_fused_dma", 0) < 1:
        raise AssertionError(f"forward did not run bcsr_fused_dma: {counts}")

    grad = jax.grad(lambda v, xx: jnp.vdot(c(v, xx), dy), argnums=(0, 1))
    (g_vals, g_x), t_grad = timed(lambda: jax.block_until_ready(
        grad(vals, x)))
    log("spmm", f"jax.grad (vals, x) first call {t_grad:.2f}s")

    (y_ref, gv_ref, gx_ref), t_ref = timed(
        lambda: ref_forward_and_grads(a, x, dy))
    log("spmm", f"ref backend forward + grads in 8 row chunks {t_ref:.2f}s")
    check("spmm", "forward", row_scaled_error(y, y_ref))
    check("spmm", "grad vals", row_scaled_error(
        np.asarray(g_vals)[:, None], gv_ref[:, None]))
    check("spmm", "grad x", row_scaled_error(g_x, gx_ref))

    before = GLOBAL_CACHE.stats()
    c2 = compile_spmm(a, d)
    after = GLOBAL_CACHE.stats()
    log("spmm", f"JitCache before {before}, after {after}")
    if c2 is not c or after["hits"] != before["hits"] + 1:
        raise AssertionError("second compile_spmm missed the JitCache")


def phase_serving(seed: int):
    import jax.numpy as jnp
    from repro.core import random_csr, spmm
    from repro.kernels import ops
    from repro.launch.serve import (SpmmRequest, SpmmResponse, SpmmScheduler,
                                    SpmmServer)

    rng = np.random.default_rng(seed)
    n = 1 << 16
    tenants = [(f"tenant{i}", random_csr(n, n, density=16 / n, family=fam,
                                         seed=seed + 10 + i), d)
               for i, (fam, d) in enumerate((("powerlaw", 128),
                                             ("uniform", 64),
                                             ("banded", 128),
                                             ("powerlaw", 64)))]
    requests = [SpmmRequest(tenant=name, a=a,
                            x=rng.standard_normal((n, d)).astype(np.float32))
                for _ in range(2) for name, a, d in tenants]
    server = SpmmServer(max_batch=4)
    log("serving", f"server backend={server.backend} "
                   f"staging={server.staging} "
                   f"interpret={server.interpret}; {len(requests)} "
                   f"requests from {len(tenants)} tenants, "
                   f"nnz {[a.nnz for _, a, _ in tenants]}")
    if server.interpret:
        raise AssertionError("serving resolved to interpret mode")
    ops.reset_dispatch_counts()
    sched = SpmmScheduler(server, max_queue_per_tenant=8)
    futures, t_submit = timed(lambda: [sched.submit(r) for r in requests])
    _, t_drain = timed(lambda: sched.close(drain=True))
    log("serving", f"submit {t_submit:.2f}s, drain {t_drain:.2f}s (compile "
                   f"included); dispatches {dict(ops.DISPATCH_COUNTS)}")
    errs = []
    for req, fut in zip(requests, futures):
        resp = fut.result(timeout=0)
        if not isinstance(resp, SpmmResponse):
            raise AssertionError(f"{req.tenant}: rejected: {resp}")
        ref = spmm(req.a, jnp.asarray(req.x), backend="ref")
        errs.append(row_scaled_error(resp.y, ref))
    check("serving", f"{len(errs)} responses", max(errs))
    log("serving", f"scheduler {sched.stats()}; server {server.stats()}")


def phase_attention(seed: int):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import compile_sparse_attention
    from repro.kernels import ops
    from repro.models.sparse_attention import sparse_attention_mask

    cfg = get_config("longformer-1.4b")
    S, dh = 4096, cfg.head_dim
    mask, t_mask = timed(lambda: sparse_attention_mask(
        S, cfg.sparse_attn_window, cfg.sparse_attn_global))
    q, k, v = (jax.random.normal(key, (S, dh), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(seed), 3))
    art, t_plan = timed(lambda: compile_sparse_attention(mask, dh))
    tags = np.asarray(art._fused.blk_tag)[np.asarray(art._fused.blk_L) > 0]
    log("attention", f"mask S={S} window={cfg.sparse_attn_window} "
                     f"global={cfg.sparse_attn_global} nnz={mask.nnz} "
                     f"({t_mask:.2f}s); compile {t_plan:.2f}s: "
                     f"backend={art.backend} staging={art.staging} "
                     f"interpret={art.interpret}; VPU/MXU descriptors "
                     f"{int((tags == 0).sum())} / {int((tags == 1).sum())}"
                     f"; {describe_panel(art)}")
    if art.interpret or art.staging != "dma":
        raise AssertionError("attention did not resolve to the staged "
                             "native kernel")
    ops.reset_dispatch_counts()
    y, t_first = timed(lambda: art(mask.vals, q, k, v).block_until_ready())
    counts = dict(ops.DISPATCH_COUNTS)
    _, t_warm = timed(lambda: art(mask.vals, q, k, v).block_until_ready())
    log("attention", f"forward first call {t_first:.2f}s, warm "
                     f"{t_warm:.4f}s; dispatches {counts}")
    if counts.get("attn_fused_dma", 0) < 1:
        raise AssertionError(f"forward did not run attn_fused_dma: {counts}")
    ref = compile_sparse_attention(mask, dh, backend="ref")
    check("attention", "forward", row_scaled_error(
        y, ref(mask.vals, q, k, v)))
    for cell, (cell_mask, cell_dh) in benchmark_attention_masks().items():
        c = compile_sparse_attention(cell_mask, cell_dh)
        log("attention", f"{cell}: nnz {cell_mask.nnz} dh {cell_dh}, "
                         f"{describe_panel(c)}; slots={c.vals_slots} "
                         f"gathered={c.vals_gather_elems}")


def phase_four_chips(seed: int):
    import jax
    import jax.numpy as jnp
    from repro.core import compile_spmm

    a = power_law_graph(seed)
    d = 128
    x = jax.random.normal(jax.random.PRNGKey(seed), (a.n, d), jnp.float32)
    vals = jnp.asarray(a.vals)
    c4, t_plan = timed(lambda: compile_spmm(a, d, backend="pallas_bcsr",
                                            n_chips=4))
    placed = slot_table_devices(c4)
    log("chips4", f"compile_spmm n_chips=4 {t_plan:.2f}s: "
                  f"staging={c4.staging} x_sharding={c4.x_sharding} "
                  f"interpret={c4.interpret} chip windows "
                  f"{c4._sharded.chip_span}; nnz={a.nnz} "
                  f"slots={c4.vals_slots} vpu_steps={c4.vpu_steps} "
                  f"gathered={c4.vals_gather_elems}; slot-value tables "
                  f"on {placed} devices")
    if any(n != 4 for n in placed) or c4.interpret:
        raise AssertionError("per-chip tables are not on four chips")
    y4, t_first = timed(lambda: jax.block_until_ready(c4(vals, x)))
    _, t_warm = timed(lambda: jax.block_until_ready(c4(vals, x)))
    log("chips4", f"forward first call {t_first:.2f}s, warm {t_warm:.4f}s")
    c1 = compile_spmm(a, d, backend="pallas_bcsr")
    y1 = jax.block_until_ready(c1(vals, x))
    y_ref = np.concatenate([
        np.asarray(jax.jit(compile_spmm(chunk, d, backend="ref"))(
            jnp.asarray(chunk.vals), x))
        for _, _, _, _, chunk in row_chunks(a, 8)])
    check("chips4", "4-chip vs ref", row_scaled_error(y4, y_ref))
    check("chips4", "4-chip vs 1-chip fused", row_scaled_error(y4, y1))
    log("chips4", f"bit-identical to 1-chip: "
                  f"{bool(np.array_equal(np.asarray(y4), np.asarray(y1)))}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.platform import use_compile_cache
    cache_dir = Path(use_compile_cache())
    entries = (lambda: len(list(cache_dir.glob("*")))  # noqa: E731
               if cache_dir.is_dir() else 0)
    cached = entries()
    log("device", f"platform={dev.platform} device_kind={dev.device_kind} "
                  f"count={len(devices)} compile_cache={cache_dir} "
                  f"({cached} entries)")
    if "repro.launch.dryrun" in sys.modules:
        raise AssertionError("the dry-run module (512 host devices) was "
                             "imported")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(args.seed)
    else:
        phase_spmm(args.seed)
        phase_serving(args.seed)
        phase_attention(args.seed)
    log("device", f"all phases passed in {time.perf_counter() - t0:.1f}s; "
                  f"compile cache {cached} -> {entries()} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
