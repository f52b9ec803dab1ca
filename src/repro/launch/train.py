"""End-to-end training driver: data pipeline -> jit'd train step ->
checkpoint/restart + watchdog straggler mitigation.

Runs real steps on whatever devices exist (CPU here: use --smoke for the
reduced configs; the full configs are exercised by the dry-run).

  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b \
      --smoke --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, reduced
from ..data.pipeline import PipelineConfig, TokenPipeline
from ..distributed.sharding import batch_shardings, param_shardings, replicated
from ..ft import checkpoint as ckpt
from ..ft.watchdog import StepTimeout, Watchdog
from ..models.model import Model
from ..optim.adamw import AdamW, warmup_cosine
from ..platform import use_compile_cache
from ..train.train_step import make_train_step
from .mesh import make_chip_mesh, make_host_mesh


def spmm_shard_preflight(n_chips: int,
                         x_sharding: str = "auto",
                         autotune: bool = False) -> int:
    """Validate the sharded fused SpMM path on this host's devices before
    committing to a long run (same ethos as the dry-run): compile a small
    sharded plan and check it against the ref backend.  Fails fast —
    asking for more chips than the host exposes raises rather than
    silently validating a smaller mesh than the run was configured for.

    It exercises block-row-aligned chip partitioning and the mixed
    VPU/MXU descriptor stream.  ``x_sharding`` selects X placement on
    the mesh ("replicated", "rows" = exact-panel fetch from owning
    chips, or "auto" — the same resolution the run itself will get), so a
    fetch-table/exchange lowering failure surfaces before step 0 too.
    ``autotune=True`` additionally runs the per-instance plan search
    (DESIGN.md §11) on the preflight fixture — warming the jit cache
    with the winner and surfacing search-path failures up front."""
    from ..core import JitCache, X_SHARDING_MODES, random_csr, spmm
    if x_sharding not in ("auto", *X_SHARDING_MODES):
        raise ValueError(
            f"--x-sharding must be 'auto' or one of {X_SHARDING_MODES}, "
            f"got {x_sharding!r}")
    avail = len(jax.devices())
    if not 1 <= n_chips <= avail:
        raise ValueError(
            f"--spmm-chips {n_chips} but only {avail} device(s) visible; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_chips} (CPU) or run on a {n_chips}-chip host")
    mesh = make_chip_mesh(n_chips)
    a = random_csr(96, 64, density=0.08, family="powerlaw", seed=0)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((64, 16)),
                    jnp.float32)
    cache = JitCache()
    # interpret=None resolves to the mode the run itself will use
    # (native on TPU, interpret on CPU) — the whole point is to surface
    # lowering failures of the real path before step 0
    y = spmm(a, x, strategy="nnz_split", backend="pallas_bcsr",
             interpret=None, mesh=mesh, x_sharding=x_sharding,
             cache=cache)
    y_ref = spmm(a, x, strategy="nnz_split", backend="ref", cache=cache)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    if autotune:
        y_t = spmm(a, x, backend="pallas_bcsr", interpret=None, mesh=mesh,
                   x_sharding=x_sharding, autotune=True, cache=cache)
        np.testing.assert_allclose(np.asarray(y_t), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
    print(f"[train] spmm shard preflight OK on {n_chips} chip(s) "
          f"(x_sharding={x_sharding}"
          f"{', autotuned' if autotune else ''})", flush=True)
    return n_chips


def sparse_attn_preflight(cfg, seq_len: int) -> None:
    """Validate the fused sparse-attention sandwich (DESIGN.md §13) for
    a config with "sattn" slots before committing to a run: build the
    run's own mask at the run's sequence length, push one (Q, K, V)
    triple through the backend the run will resolve ("auto": fused
    pallas on TPU, ref elsewhere) and check it against the pure-jnp
    oracle.  Surfaces descriptor-stream lowering failures before
    step 0, exactly like ``spmm_shard_preflight`` does for SpMM."""
    from ..core import compile_sparse_attention
    from ..models.sparse_attention import sparse_attention_mask
    S = min(seq_len, 128)
    a = sparse_attention_mask(S, cfg.sparse_attn_window,
                              cfg.sparse_attn_global)
    rng = np.random.default_rng(0)
    hd = cfg.head_dim
    q, k, v = (jnp.asarray(rng.standard_normal((S, hd)), jnp.float32)
               for _ in range(3))
    vals = jnp.ones((a.nnz,), jnp.float32)
    y = compile_sparse_attention(a, hd)(vals, q, k, v)
    y_ref = compile_sparse_attention(a, hd, backend="ref")(vals, q, k, v)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    print(f"[train] sparse-attention preflight OK "
          f"(S={S}, window={cfg.sparse_attn_window}, "
          f"global={cfg.sparse_attn_global}, nnz={a.nnz})", flush=True)


def run_training(cfg, *, steps: int, global_batch: int, seq_len: int,
                 ckpt_dir=None, ckpt_every: int = 20, lr: float = 3e-4,
                 microbatches: int = 1, remat: str = "full",
                 data_parallel: int = 1, model_parallel: int = 1,
                 spmm_chips: int = 0,
                 spmm_x_sharding: str = "auto", spmm_autotune: bool = False,
                 log_every: int = 10,
                 fault_injector=None, watchdog: Watchdog = None,
                 seed: int = 0, stop_at: int = None):
    model = Model(cfg)
    if spmm_chips:
        # the sparse-aggregation chips share the host devices with the
        # train mesh; fail fast here rather than mid-run
        spmm_shard_preflight(spmm_chips, spmm_x_sharding,
                             autotune=spmm_autotune)
    if "sattn" in cfg.pattern:
        sparse_attn_preflight(cfg, seq_len)
    mesh = make_host_mesh(data=data_parallel, model=model_parallel)
    opt = AdamW(learning_rate=warmup_cosine(lr, min(20, steps // 10 + 1),
                                            steps))

    param_sds = jax.eval_shape(model.init, jax.random.PRNGKey(seed))
    p_shard = param_shardings(param_sds, mesh)
    opt_sds = jax.eval_shape(opt.init, param_sds)
    o_shard = param_shardings(opt_sds, mesh)

    step_fn = make_train_step(
        model, opt, remat=remat, microbatches=microbatches,
        chunk_q=max(64, seq_len // 4),
        shard_ctx={"mesh": mesh, "dp": ("data",)})

    pipe_cfg = PipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=seq_len,
        global_batch=global_batch, seed=seed,
        num_image_tokens=cfg.num_image_tokens
        if cfg.family == "vlm" else 0, d_model=cfg.d_model)
    pipe = TokenPipeline(pipe_cfg)

    batch_sds = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), pipe.batch_at(0))
    b_shard = batch_shardings(batch_sds, mesh)
    metrics_shard = {k: replicated(mesh)
                     for k in ("loss", "grad_norm", "nll")}
    jitted = jax.jit(step_fn, in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, metrics_shard),
                     donate_argnums=(0, 1))

    # init or resume.  Init is compiled WITHOUT out_shardings and then
    # distributed: partitioned compilation of the legacy (non-
    # partitionable) threefry RNG draws different bits per mesh shape,
    # so jit(init, out_shardings=...) would make the starting params a
    # function of the device grid (observed: 2x4 vs 1x1 diverge from
    # step 0).  device_put after the fact is sharding-transparent.
    start_step = 0
    params = jax.device_put(
        jax.jit(model.init)(jax.random.PRNGKey(seed)), p_shard)
    opt_state = jax.device_put(jax.jit(opt.init)(params), o_shard)
    if ckpt_dir is not None and ckpt.latest_step(ckpt_dir) is not None:
        start_step = ckpt.latest_step(ckpt_dir)
        params = ckpt.restore_checkpoint(ckpt_dir, param_sds,
                                         shardings=p_shard)
        opt_state = ckpt.restore_checkpoint(
            Path(ckpt_dir) / "opt", opt_sds, shardings=o_shard)
        print(f"[train] resumed from step {start_step}", flush=True)

    wd = watchdog or Watchdog()
    losses = []
    step = start_step
    end_step = min(steps, stop_at) if stop_at is not None else steps
    while step < end_step:
        batch = jax.tree.map(
            lambda x, s: jax.device_put(x, s), pipe.batch_at(step), b_shard)
        try:
            params, opt_state, metrics = wd.run_step(
                jitted, params, opt_state, batch,
                fault_injector=fault_injector)
        except StepTimeout as e:
            print(f"[train] step {step}: {e}; restoring last checkpoint",
                  flush=True)
            if ckpt_dir is None or ckpt.latest_step(ckpt_dir) is None:
                # nothing to restore; re-init optimizer step only
                continue
            step = ckpt.latest_step(ckpt_dir)
            params = ckpt.restore_checkpoint(ckpt_dir, param_sds,
                                             shardings=p_shard)
            opt_state = ckpt.restore_checkpoint(
                Path(ckpt_dir) / "opt", opt_sds, shardings=o_shard)
            continue
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % log_every == 0:
            print(f"[train] step {step} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
        step += 1
        if ckpt_dir is not None and step % ckpt_every == 0:
            ckpt.save_checkpoint(ckpt_dir, step, params)
            ckpt.save_checkpoint(Path(ckpt_dir) / "opt", step, opt_state)
    if ckpt_dir is not None:
        ckpt.save_checkpoint(ckpt_dir, step, params)
        ckpt.save_checkpoint(Path(ckpt_dir) / "opt", step, opt_state)
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--spmm-chips", type=int, default=0,
                    help="validate the sharded fused SpMM path on this "
                         "many chips before training (0 = skip)")
    ap.add_argument("--x-sharding", default="auto",
                    choices=["auto", "replicated", "rows"],
                    help="X placement the preflight validates on the "
                         "chip mesh: replicated per chip, or rows = "
                         "exact-panel fetch from owning chips "
                         "(DESIGN.md §7.8); auto matches the run")
    ap.add_argument("--autotune", action="store_true",
                    help="preflight also runs the per-instance SpMM "
                         "plan search (strategy x merge x staging, "
                         "DESIGN.md §11) and validates + caches the "
                         "winning config")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    t0 = time.time()
    _, losses = run_training(
        cfg, steps=args.steps, global_batch=args.batch, seq_len=args.seq,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every, lr=args.lr,
        microbatches=args.microbatches, remat=args.remat,
        data_parallel=args.dp, model_parallel=args.tp,
        spmm_chips=args.spmm_chips,
        spmm_x_sharding=args.x_sharding, spmm_autotune=args.autotune)
    print(f"[train] done: first loss {losses[0]:.4f} "
          f"last loss {losses[-1]:.4f} ({time.time()-t0:.1f}s)")


if __name__ == "__main__":
    main()
