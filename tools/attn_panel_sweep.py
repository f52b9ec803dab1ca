"""Chip timings of the fused attention trip at each MXU panel edge.

For the two attention masks the chip benchmark runs (Mellum2's causal
window of 1,024 at head dim 128, and Longformer-large's two-sided
window of 512 with 64 global tokens at head dim 64, both at S = 4,096)
and each square edge of ``repro.core.plan.PANEL_EDGES``, this builds
the artifact with that panel pinned and prints one JSON line:

  panels, fill     MXU panels per call and nonzeros over their slots
  kernel_ms        the ``pallas_call`` alone on staged operands
  stage_ms         the slot-value staging (``_SlotValues.stage``)
  call_ms          the whole eager artifact call
  gap              the widest row-scaled gap to the artifact's jnp
                   reference

each per call, as the median of ``--rounds`` host-clock rounds of
``--reps`` calls that end in ``block_until_ready``.  At edge 8 a second
line times the kernel with its VPU branch traced in (``vpu``), as a
plan with VPU trips lowers it.  The last line is the edge
``choose_panel_edge`` picks for each mask.  These are the numbers
``MIN_PANEL_FILL`` is set from (PERF.md section 5).

    python3 tools/attn_panel_sweep.py [--reps 20] [--rounds 3]

It needs a TPU and refuses to run on anything else.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def longformer_mask(S: int, window: int, global_tokens: int):
    """The benchmark's own Longformer generator, loaded by path."""
    path = ROOT / "chipbench" / "gen" / "longformer_mask.py"
    spec = importlib.util.spec_from_file_location("longformer_mask", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate({"seq_len": S, "attention_window": window,
                         "global_tokens": global_tokens})


def masks():
    from repro.core import CSRMatrix
    from repro.models.sparse_attention import sparse_attention_mask
    S = 4096
    rp, cols, shape = longformer_mask(S, 512, 64)
    longformer = CSRMatrix(shape, rp, cols,
                           jnp.ones((cols.size,), jnp.float32))
    return {"mellum_window_1024": (sparse_attention_mask(S, 1024, 0), 128),
            "longformer_512_g64": (longformer, 64)}


def per_call_ms(fn, reps: int, rounds: int) -> float:
    jax.block_until_ready(fn())
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / reps * 1e3)
    return statistics.median(times)


def row_scaled_gap(y, ref) -> float:
    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    scale = np.abs(ref).max(axis=1)
    scale = np.maximum(scale, np.median(scale))
    return float((np.abs(y - ref).max(axis=1) / scale).max())


def sweep(name, mask, dh, b, seed, reps, rounds):
    from repro.core import compile_sparse_attention
    from repro.core.jit_cache import JitCache
    from repro.kernels import ops

    art = compile_sparse_attention(mask, dh, bm=b, bk=b, cache=JitCache())
    if art.interpret or art.staging != "dma" or art.backend != "pallas_bcsr":
        raise SystemExit(f"{name}: not the staged native kernel")
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q, k, v = (jax.random.normal(kk, (mask.shape[0], dh), jnp.float32)
               for kk in keys)
    vals = jnp.asarray(mask.vals)
    fw = art._fused
    vals_flat = fw.vals.stage(vals)
    q_ext, k_pad, v_pad = art._operands(q, k, v)
    q_ws = q_ext[art._row_map]

    def kernel_fn(vpu):
        return jax.jit(lambda vf, qw, kp, vp: ops.attn_fused_op(
            fw.blk_tag, fw.blk_off, fw.blk_coff, fw.blk_L, fw.cols_flat,
            vf, qw, kp, vp, fw.cont, bm=b, bk=b, mw=fw.merge_width,
            vpu=vpu, interpret=False, staging="dma", span=fw.max_span,
            cspan=fw.max_cspan))

    kern = kernel_fn(art._vpu)
    y = art(vals, q, k, v)
    ref = jax.jit(art._ref_forward)(vals, q, k, v)
    line = {
        "mask": name, "dh": dh, "b": b, "vpu": art._vpu,
        "panels": art.mxu_panels, "fill": art.panel_fill,
        "descriptors": fw.num_blocks, "slots": art.vals_slots,
        "gathered": art.vals_gather_elems,
        "kernel_ms": per_call_ms(
            lambda: kern(vals_flat, q_ws, k_pad, v_pad), reps, rounds),
        "stage_ms": per_call_ms(lambda: fw.vals.stage(vals), reps, rounds),
        "call_ms": per_call_ms(lambda: art(vals, q, k, v), reps, rounds),
        "gap": row_scaled_gap(y, ref),
        "device": jax.devices()[0].device_kind,
    }
    lines = [line]
    if b == 8 and not art._vpu:
        with_vpu = kernel_fn(True)
        lines.append({"mask": name, "dh": dh, "b": b, "vpu": True,
                      "kernel_ms": per_call_ms(
                          lambda: with_vpu(vals_flat, q_ws, k_pad, v_pad),
                          reps, rounds),
                      "device": line["device"]})
    for ln in lines:
        print(json.dumps(ln), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=16)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("attn_panel_sweep needs a TPU")
    from repro.core.plan import PANEL_EDGES, choose_panel_edge
    from repro.platform import use_compile_cache
    use_compile_cache()
    chosen = {}
    for name, (mask, dh) in masks().items():
        for b in PANEL_EDGES:
            sweep(name, mask, dh, b, args.seed, args.reps, args.rounds)
        chosen[name] = choose_panel_edge(mask.row_ptr, mask.col_indices,
                                         mask.shape)
    print(json.dumps({"chosen": chosen}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
