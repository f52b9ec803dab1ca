"""Unified decoder stack for all 10 assigned architectures.

Depth is organized as ``num_periods`` repetitions of the config's layer
``pattern`` (period); parameters are stacked over periods and the stack
is applied with ``lax.scan`` so the lowered HLO contains ONE period body
regardless of depth (compile-time discipline for the 126-layer cells).
Heterogeneous patterns (jamba's 7:1 mamba:attn, the VLM's 1-in-5
cross-attn) unroll *within* the period body.

Three entry points:
  forward_train   full-sequence forward -> (logits, aux)
  prefill         forward + cache construction -> (logits, caches)
  forward_decode  one token against caches -> (logits, new caches)
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import layers, mamba, moe, rwkv6, sparse_attention

# sentinel position for unfilled KV-cache slots: +2^30 fails the causal
# test (qpos >= kvpos) so empty slots never attend
UNFILLED_POS = jnp.int32(2 ** 30)


def _gather_fsdp(period_params, shard_ctx):
    """Explicit per-layer FSDP gather (ZeRO-3 'gather at use').

    Without this GSPMD keeps weights sharded on the fsdp (data) axis and
    contracts the sharded d_model dim directly — all-reducing full
    (B,S,D) f32 activations several times per layer (~GBs) instead of
    all-gathering the MB-scale weight shards.  Constraining the sliced
    period params to their TP-only spec inside the scan body forces the
    gather just-in-time, bounding live gathered memory to one period.
    """
    if shard_ctx is None or not shard_ctx.get("gather_fsdp"):
        return period_params
    from jax.sharding import NamedSharding, PartitionSpec
    from ..distributed.sharding import AxisEnv, param_pspec
    mesh = shard_ctx["mesh"]
    env = AxisEnv(mesh)

    def leaf(path, x):
        spec = param_pspec(path, x.shape, env)
        spec = PartitionSpec(*[None if sp in ("data", ("data",)) else sp
                               for sp in spec])
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))
    return jax.tree_util.tree_map_with_path(leaf, period_params)


def _constrain(x, shard_ctx, spec):
    """Activation sharding constraint.  Without these GSPMD follows the
    *parameter* shardings into the residual stream (e.g. the embedding's
    FSDP dim) and replicates the batch across the data axis — 16x the
    FLOPs.  spec entries: "DP" -> the batch axes, or a mesh axis name /
    None.  Dims that don't divide are left unconstrained (long_500k
    batch=1 relies on this to fall back to sequence sharding)."""
    if shard_ctx is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    mesh, dp = shard_ctx["mesh"], shard_ctx["dp"]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    resolved = []
    for dim, s_ in enumerate(spec):
        if s_ is None:
            resolved.append(None)
            continue
        axes = dp if s_ == "DP" else (s_,)
        n = 1
        for a in axes:
            n *= sizes[a]
        if x.shape[dim] % n == 0 and x.shape[dim] > 0:
            resolved.append(axes if len(axes) > 1 else axes[0])
        else:
            resolved.append(None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*resolved)))


# ---------------------------------------------------------------------------
# Parameter init (per slot kind), vmapped over periods
# ---------------------------------------------------------------------------

def _dtype(cfg: ArchConfig):
    return jnp.dtype(cfg.dtype)


def _norm(rng, d, dt):
    return jnp.ones((d,), dt)


def _init_attn(cfg: ArchConfig, rng, dt):
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 8)
    s = 0.02
    so = 0.02 / (2 * cfg.num_layers) ** 0.5
    p = {
        "ln": jnp.ones((D,), dt),
        "wq": jax.random.normal(ks[0], (D, H, hd), dt) * s,
        "wk": jax.random.normal(ks[1], (D, KV, hd), dt) * s,
        "wv": jax.random.normal(ks[2], (D, KV, hd), dt) * s,
        "wo": jax.random.normal(ks[3], (H, hd, D), dt) * so,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dt)
        p["bk"] = jnp.zeros((KV, hd), dt)
        p["bv"] = jnp.zeros((KV, hd), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def _init_xattn(cfg: ArchConfig, rng, dt):
    p = _init_attn(cfg, rng, dt)
    p["ln_kv"] = jnp.ones((cfg.d_model,), dt)
    p["gate"] = jnp.zeros((), dt)
    return p


def _init_dense_ffn(cfg: ArchConfig, rng, dt):
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 3)
    s = 0.02
    so = 0.02 / (2 * cfg.num_layers) ** 0.5
    return {
        "ln": jnp.ones((D,), dt),
        "w_gate": jax.random.normal(ks[0], (D, F), dt) * s,
        "w_up": jax.random.normal(ks[1], (D, F), dt) * s,
        "w_down": jax.random.normal(ks[2], (F, D), dt) * so,
    }


def _init_moe_ffn(cfg: ArchConfig, rng, dt):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    n = E if cfg.experts_held is None else cfg.experts_held[1]
    ks = jax.random.split(rng, 4)
    s = 0.02
    so = 0.02 / (2 * cfg.num_layers) ** 0.5
    return {
        "ln": jnp.ones((D,), dt),
        "router": jax.random.normal(ks[0], (D, E), jnp.float32) * s,
        "w_gate": jax.random.normal(ks[1], (n, D, F), dt) * s,
        "w_up": jax.random.normal(ks[2], (n, D, F), dt) * s,
        "w_down": jax.random.normal(ks[3], (n, F, D), dt) * so,
    }


def _init_mamba(cfg: ArchConfig, rng, dt):
    D = cfg.d_model
    Di, N = cfg.mamba_d_inner, cfg.mamba_state
    R, K = cfg.mamba_dt_rank, cfg.mamba_conv
    ks = jax.random.split(rng, 6)
    s = 0.02
    dt_init = jnp.exp(jax.random.uniform(
        ks[5], (Di,), jnp.float32,
        jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "ln": jnp.ones((D,), dt),
        "in_proj": jax.random.normal(ks[0], (D, 2 * Di), dt) * s,
        "conv_w": jax.random.normal(ks[1], (K, Di), dt) * s,
        "conv_b": jnp.zeros((Di,), dt),
        "x_proj": jax.random.normal(ks[2], (Di, R + 2 * N), dt) * s,
        "dt_proj": jax.random.normal(ks[3], (R, Di), dt) * (R ** -0.5),
        "dt_bias": jnp.log(jnp.expm1(dt_init)),                 # f32
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32), (Di, N))),
        "D": jnp.ones((Di,), jnp.float32),
        "out_proj": jax.random.normal(ks[4], (Di, D), dt)
        * (0.02 / (2 * cfg.num_layers) ** 0.5),
    }


def _init_rwkv(cfg: ArchConfig, rng, dt):
    D, F = cfg.d_model, cfg.d_ff
    H, N = cfg.num_heads, cfg.head_dim
    ks = jax.random.split(rng, 24)
    s = 0.02
    tm = {"ln_w": jnp.ones((D,), dt), "ln_b": jnp.zeros((D,), dt),
          "u": jax.random.normal(ks[0], (H, N), jnp.float32) * s,
          "w0": jnp.full((H, N), -5.0, jnp.float32),
          "gn_w": jnp.ones((H, N), jnp.float32),
          "gn_b": jnp.zeros((H, N), jnp.float32)}
    for i, nm in enumerate(("r", "k", "v", "g")):
        tm[f"mu_{nm}"] = jnp.full((D,), 0.5, dt)
        tm[f"lora_{nm}_a"] = jax.random.normal(ks[1 + i], (D, 32), jnp.float32) * s
        tm[f"lora_{nm}_b"] = jax.random.normal(ks[5 + i], (32, D), jnp.float32) * s
        tm[f"w_{nm}"] = jax.random.normal(ks[9 + i], (D, H, N), dt) * s
    tm["mu_w"] = jnp.full((D,), 0.5, dt)
    tm["lora_w_a"] = jax.random.normal(ks[13], (D, 64), jnp.float32) * s
    tm["lora_w_b"] = jax.random.normal(ks[14], (64, D), jnp.float32) * s
    tm["w_o"] = jax.random.normal(ks[15], (H, N, D), dt) \
        * (0.02 / (2 * cfg.num_layers) ** 0.5)
    cm = {"ln_w": jnp.ones((D,), dt), "ln_b": jnp.zeros((D,), dt),
          "mu_k": jnp.full((D,), 0.5, dt), "mu_r": jnp.full((D,), 0.5, dt),
          "w_k": jax.random.normal(ks[16], (D, F), dt) * s,
          "w_v": jax.random.normal(ks[17], (F, D), dt) * s,
          "w_r": jax.random.normal(ks[18], (D, D), dt) * s}
    return {"tm": tm, "cm": cm}


# "sattn" (sparse attention, DESIGN.md §13) reuses the attn projection
# stack verbatim — only the attend step differs (the fused descriptor-
# stream sandwich in train and prefill, dense masked in decode)
_SLOT_INIT = {"attn": _init_attn, "xattn": _init_xattn,
              "sattn": _init_attn,
              "mamba": _init_mamba, "rwkv": _init_rwkv}
_FFN_INIT = {"dense": _init_dense_ffn, "moe": _init_moe_ffn}


def init_params(cfg: ArchConfig, rng) -> Dict[str, Any]:
    dt = _dtype(cfg)
    rngs = jax.random.split(rng, 4 + cfg.period_len)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(rngs[0], (cfg.vocab_size, cfg.d_model),
                                   dt) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), dt),
        "lm_head": jax.random.normal(rngs[1], (cfg.d_model, cfg.vocab_size),
                                     dt) * 0.02,
        "period": {},
    }
    for i, kind in enumerate(cfg.pattern):
        def one(r, kind=kind, i=i):
            r1, r2 = jax.random.split(r)
            slot = {kind: _SLOT_INIT[kind](cfg, r1, dt)}
            fk = cfg.ffn_kind(i)
            if fk != "none":
                slot["ffn_" + fk] = _FFN_INIT[fk](cfg, r2, dt)
            return slot
        period_rngs = jax.random.split(rngs[4 + i], cfg.num_periods)
        params["period"][f"slot{i}"] = jax.vmap(one)(period_rngs)
    return params


# ---------------------------------------------------------------------------
# Slot application
# ---------------------------------------------------------------------------

def _apply_ffn(cfg, slot_params, x, shard_ctx=None):
    aux = {}
    if "ffn_dense" in slot_params:
        x = layers.swiglu_mlp(slot_params["ffn_dense"], x,
                              norm_eps=cfg.norm_eps)
    elif "ffn_moe" in slot_params:
        x, aux = moe.moe_ffn(slot_params["ffn_moe"], x,
                             num_experts=cfg.num_experts, top_k=cfg.top_k,
                             capacity_factor=cfg.capacity_factor,
                             experts_held=cfg.experts_held,
                             norm_eps=cfg.norm_eps, shard_ctx=shard_ctx)
    return x, aux


def _apply_slot_train(cfg: ArchConfig, kind: str, slot_params, x, positions,
                      image_embeds, chunk_q, ssm_chunk=256,
                      unroll_chunks=False, shard_ctx=None,
                      causal_skip=False):
    if kind == "attn":
        with jax.named_scope("attn"):
            x = layers.self_attention_layer(
                slot_params["attn"], x, positions=positions,
                head_dim=cfg.head_dim, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, rope_theta=cfg.rope_theta,
                rope_yarn=cfg.rope_yarn, causal=True,
                window=cfg.sliding_window, qk_norm=cfg.qk_norm,
                norm_eps=cfg.norm_eps, chunk_q=chunk_q,
                unroll_chunks=unroll_chunks, causal_skip=causal_skip)
    elif kind == "sattn":
        with jax.named_scope("sattn"):
            x = sparse_attention.sparse_self_attention_layer(
                slot_params["sattn"], x, positions=positions,
                head_dim=cfg.head_dim, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads,
                window=cfg.sparse_attn_window,
                num_global=cfg.sparse_attn_global,
                rope_theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                norm_eps=cfg.norm_eps)
    elif kind == "xattn":
        x = layers.cross_attention_layer(
            slot_params["xattn"], x, image_embeds, head_dim=cfg.head_dim,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps, chunk_q=chunk_q,
            unroll_chunks=unroll_chunks)
    elif kind == "mamba":
        x = mamba.mamba_block(slot_params["mamba"], x,
                              state_dim=cfg.mamba_state,
                              conv_width=cfg.mamba_conv,
                              chunk=ssm_chunk,
                              norm_eps=cfg.norm_eps)
    elif kind == "rwkv":
        x = rwkv6.rwkv_block(slot_params["rwkv"], x,
                             num_heads=cfg.num_heads, head_dim=cfg.head_dim,
                             chunk=ssm_chunk, norm_eps=cfg.norm_eps)
    else:
        raise ValueError(kind)
    return _apply_ffn(cfg, slot_params, x, shard_ctx)


# ---------------------------------------------------------------------------
# Train forward
# ---------------------------------------------------------------------------

def forward_train(cfg: ArchConfig, params, tokens, *, image_embeds=None,
                  remat: str = "full", chunk_q: int = 512,
                  ssm_chunk: int = 256, scan_unroll: bool = False,
                  unroll_chunks: bool = False, logits_f32: bool = True,
                  shard_ctx=None, causal_skip: bool = False):
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = _constrain(x, shard_ctx, ("DP", None, None))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def period_body(x, period_params):
        x = _constrain(x, shard_ctx, ("DP", None, None))
        period_params = _gather_fsdp(period_params, shard_ctx)
        aux_total = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(cfg.pattern):
            x, aux = _apply_slot_train(cfg, kind, period_params[f"slot{i}"],
                                       x, positions, image_embeds, chunk_q,
                                       ssm_chunk, unroll_chunks, shard_ctx,
                                       causal_skip)
            if shard_ctx and shard_ctx.get("bf16_ar"):
                # barrier stops XLA hoisting the next norm's f32 convert
                # above the Megatron all-reduce (keeps the AR in bf16 —
                # halves the dominant collective's bytes)
                x = jax.lax.optimization_barrier(x)
            if aux:
                aux_total = aux_total + aux["moe_lb_loss"] \
                    + 1e-3 * aux["moe_z_loss"]
        return x, aux_total

    if remat == "full":
        period_body = jax.checkpoint(
            period_body, policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        period_body = jax.checkpoint(
            period_body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    def scan_body(carry, period_params):
        x, new_aux = period_body(carry, period_params)
        return x, new_aux

    x, aux_stack = jax.lax.scan(scan_body, x, params["period"],
                                unroll=scan_unroll)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    logits = _constrain(logits, shard_ctx, ("DP", None, "model"))
    if logits_f32:
        logits = logits.astype(jnp.float32)
    return logits, {"moe_aux": jnp.sum(aux_stack)}


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

def attn_cache_len(cfg: ArchConfig, cache_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, cache_len)
    return cache_len


def init_decode_cache(cfg: ArchConfig, batch: int, cache_len: int):
    """Zero caches (stacked over periods) for decode; shapes only matter
    for the dry-run, contents for real serving (filled by prefill)."""
    dt = _dtype(cfg)
    P = cfg.num_periods
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    caches = {}
    for i, kind in enumerate(cfg.pattern):
        if kind in ("attn", "sattn"):
            # sattn keeps the FULL cache: rolling window eviction would
            # drop the global tokens every later query must still see
            T = cache_len if kind == "sattn" \
                else attn_cache_len(cfg, cache_len)
            caches[f"slot{i}"] = {
                "k": jnp.zeros((P, batch, T, KV, hd), dt),
                "v": jnp.zeros((P, batch, T, KV, hd), dt),
                "kpos": jnp.full((P, batch, T), UNFILLED_POS, jnp.int32),
            }
        elif kind == "xattn":
            n_img = cfg.num_image_tokens
            caches[f"slot{i}"] = {
                "xk": jnp.zeros((P, batch, n_img, KV, hd), dt),
                "xv": jnp.zeros((P, batch, n_img, KV, hd), dt),
            }
        elif kind == "mamba":
            Di, N, K = cfg.mamba_d_inner, cfg.mamba_state, cfg.mamba_conv
            caches[f"slot{i}"] = {
                "ssm": jnp.zeros((P, batch, Di, N), jnp.float32),
                "conv": jnp.zeros((P, batch, K - 1, Di), dt),
            }
        elif kind == "rwkv":
            H, N, D = cfg.num_heads, cfg.head_dim, cfg.d_model
            caches[f"slot{i}"] = {
                "wkv": jnp.zeros((P, batch, H, N, N), jnp.float32),
                "x_prev_tm": jnp.zeros((P, batch, D), dt),
                "x_prev_cm": jnp.zeros((P, batch, D), dt),
            }
    return caches


# ---------------------------------------------------------------------------
# Decode step (one new token against the caches)
# ---------------------------------------------------------------------------

def _decode_attn(cfg, p, x, cache, pos, *, window=None, num_global=0,
                 yarn=None):
    B = x.shape[0]
    h = layers.rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = layers.attn_project_qkv(p, h, cfg.num_heads, cfg.num_kv_heads,
                                      cfg.head_dim, qk_norm=cfg.qk_norm,
                                      norm_eps=cfg.norm_eps)
    posb = jnp.broadcast_to(pos[None, None], (B, 1)).astype(jnp.int32)
    q = layers.apply_rope(q, posb, cfg.rope_theta, yarn)
    k = layers.apply_rope(k, posb, cfg.rope_theta, yarn)
    T = cache["k"].shape[1]
    idx = (pos % T).astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, idx, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, idx, 0, 0))
    ckpos = jax.lax.dynamic_update_slice(cache["kpos"],
                                         posb.astype(jnp.int32), (0, idx))
    out = layers.gqa_attention(q, ck, cv, q_positions=posb,
                               kv_positions=ckpos, causal=True,
                               window=window, num_global=num_global)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return x + out, {"k": ck, "v": cv, "kpos": ckpos}


def _decode_xattn(cfg, p, x, cache):
    h = layers.rms_norm(x, p["ln"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(x.dtype))
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"], cfg.norm_eps)
    B = x.shape[0]
    n_img = cache["xk"].shape[1]
    qpos = jnp.zeros((B, 1), jnp.int32)
    kpos = jnp.zeros((B, n_img), jnp.int32)
    out = layers.gqa_attention(q, cache["xk"], cache["xv"],
                               q_positions=qpos, kv_positions=kpos,
                               causal=False)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    gate = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype)
    return x + gate * out, cache


def forward_decode(cfg: ArchConfig, params, token, caches, pos, *,
                   scan_unroll: bool = False, shard_ctx=None):
    """token (B,1) int32; pos scalar int32; caches from init/prefill."""
    x = jnp.take(params["embed"], token, axis=0)
    x = _constrain(x, shard_ctx, ("DP", None, None))

    def period_body(x, scanned):
        x = _constrain(x, shard_ctx, ("DP", None, None))
        period_params, cache_p = scanned
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            sp = period_params[f"slot{i}"]
            if kind == "attn":
                x, nc = _decode_attn(cfg, sp["attn"], x,
                                     cache_p[f"slot{i}"], pos,
                                     window=cfg.sliding_window,
                                     yarn=cfg.rope_yarn)
            elif kind == "sattn":
                # serve-side fallback: dense masked attention with the
                # SAME window+global mask the fused train path encodes
                # in its CSR structure (softmax-over-present-entries
                # semantics coincide — the diagonal is always present)
                x, nc = _decode_attn(cfg, sp["sattn"], x,
                                     cache_p[f"slot{i}"], pos,
                                     window=cfg.sparse_attn_window,
                                     num_global=cfg.sparse_attn_global)
            elif kind == "xattn":
                x, nc = _decode_xattn(cfg, sp["xattn"], x, cache_p[f"slot{i}"])
            elif kind == "mamba":
                x, nc = mamba.mamba_block(
                    sp["mamba"], x, state_dim=cfg.mamba_state,
                    conv_width=cfg.mamba_conv, norm_eps=cfg.norm_eps,
                    init_state=cache_p[f"slot{i}"], return_state=True)
            elif kind == "rwkv":
                x, nc = rwkv6.rwkv_block(
                    sp["rwkv"], x, num_heads=cfg.num_heads,
                    head_dim=cfg.head_dim, norm_eps=cfg.norm_eps,
                    init_state=cache_p[f"slot{i}"], return_state=True)
            new_caches[f"slot{i}"] = nc
            x, _ = _apply_ffn(cfg, sp, x, shard_ctx)
        return x, new_caches

    x, new_caches = jax.lax.scan(period_body, x,
                                 (params["period"], caches),
                                 unroll=scan_unroll)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    logits = _constrain(logits, shard_ctx, ("DP", None, "model"))
    return logits.astype(jnp.float32), new_caches


# ---------------------------------------------------------------------------
# Prefill (forward + cache build) — serving path
# ---------------------------------------------------------------------------

def _prefill_attn(cfg: ArchConfig, kind: str, p, x, positions,
                  cache_len: int, *, chunk_q: int, unroll_chunks: bool,
                  causal_skip: bool):
    """One attn or sattn slot over the whole prompt, and its KV cache.

    attn slots attend densely (RoPE with ``cfg.rope_yarn``) and keep
    the last ``attn_cache_len`` positions; sattn slots attend through
    the fused sparse-attention artifact (plain RoPE), as the train
    forward does, and keep a full-length cache: global tokens must
    survive, so there is no windowed eviction."""
    B, S = positions.shape
    h = layers.rms_norm(x, p["ln"], cfg.norm_eps)
    q, k, v = layers.attn_project_qkv(
        p, h, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps)
    yarn = cfg.rope_yarn if kind == "attn" else None
    q = layers.apply_rope(q, positions, cfg.rope_theta, yarn)
    k = layers.apply_rope(k, positions, cfg.rope_theta, yarn)
    if kind == "sattn":
        out = sparse_attention.sparse_attend(
            q, k, v, window=cfg.sparse_attn_window,
            num_global=cfg.sparse_attn_global)
        T = cache_len
    else:
        if causal_skip:
            out = layers.gqa_attention_causal_skip(
                q, k, v, q_positions=positions, kv_positions=positions,
                window=cfg.sliding_window, chunk_q=chunk_q)
        else:
            out = layers.gqa_attention(
                q, k, v, q_positions=positions, kv_positions=positions,
                causal=True, window=cfg.sliding_window, chunk_q=chunk_q,
                unroll_chunks=unroll_chunks)
        T = attn_cache_len(cfg, cache_len)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    keep = min(S, T)
    ck = jnp.zeros((B, T) + k.shape[2:], k.dtype
                   ).at[:, :keep].set(k[:, -keep:])
    cv = jnp.zeros((B, T) + v.shape[2:], v.dtype
                   ).at[:, :keep].set(v[:, -keep:])
    ckpos = jnp.full((B, T), UNFILLED_POS, jnp.int32
                     ).at[:, :keep].set(positions[:, -keep:])
    return x + out, {"k": ck, "v": cv, "kpos": ckpos}


def prefill(cfg: ArchConfig, params, tokens, cache_len: int, *,
            image_embeds=None, chunk_q: int = 512, ssm_chunk: int = 256,
            scan_unroll: bool = False, unroll_chunks: bool = False,
            shard_ctx=None, causal_skip: bool = False):
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = _constrain(x, shard_ctx, ("DP", None, None))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def period_body(x, period_params):
        x = _constrain(x, shard_ctx, ("DP", None, None))
        period_params = _gather_fsdp(period_params, shard_ctx)
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            sp = period_params[f"slot{i}"]
            if kind in ("attn", "sattn"):
                with jax.named_scope(kind):
                    x, new_caches[f"slot{i}"] = _prefill_attn(
                        cfg, kind, sp[kind], x, positions, cache_len,
                        chunk_q=chunk_q, unroll_chunks=unroll_chunks,
                        causal_skip=causal_skip)
            elif kind == "xattn":
                p = sp["xattn"]
                kv = layers.rms_norm(image_embeds, p["ln_kv"], cfg.norm_eps)
                xk = jnp.einsum("bsd,dhk->bshk", kv, p["wk"].astype(x.dtype))
                xv = jnp.einsum("bsd,dhk->bshk", kv, p["wv"].astype(x.dtype))
                if cfg.qk_norm:
                    xk = layers.rms_norm(xk, p["k_norm"], cfg.norm_eps)
                x = layers.cross_attention_layer(
                    p, x, image_embeds, head_dim=cfg.head_dim,
                    num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                    qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps,
                    chunk_q=chunk_q, unroll_chunks=unroll_chunks)
                new_caches[f"slot{i}"] = {"xk": xk, "xv": xv}
            elif kind == "mamba":
                x, st = mamba.mamba_block(
                    sp["mamba"], x, state_dim=cfg.mamba_state,
                    conv_width=cfg.mamba_conv, chunk=ssm_chunk,
                    norm_eps=cfg.norm_eps, return_state=True)
                new_caches[f"slot{i}"] = st
            elif kind == "rwkv":
                x, st = rwkv6.rwkv_block(
                    sp["rwkv"], x, num_heads=cfg.num_heads,
                    head_dim=cfg.head_dim, chunk=ssm_chunk,
                    norm_eps=cfg.norm_eps, return_state=True)
                new_caches[f"slot{i}"] = st
            x, _ = _apply_ffn(cfg, sp, x, shard_ctx)
        return x, new_caches

    x, caches = jax.lax.scan(period_body, x, params["period"],
                             unroll=scan_unroll)
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(x.dtype))
    logits = _constrain(logits, shard_ctx, ("DP", None, "model"))
    return logits.astype(jnp.float32), caches
