"""Plain jnp reference of the decoder stack's forward, for the CPU
tests: configurations made of ``attn`` / ``sattn`` slots with MoE or
dense SwiGLU FFNs (Mellum2's shape).  Float32 at matmul precision
``highest``; no kernels, cache, scan or capacity; one layer after the
other, attention as dense masked softmax over whole rows.

It reads the program's parameter tree (stacked over periods) and
imports nothing of the program.  RoPE is Hugging Face's (rotate_half),
with YaRN on the full-attention slots where the configuration gives
it (``hf_yarn_inv_freq``, a transcription of HF's
``_compute_yarn_parameters``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def hf_yarn_inv_freq(dim, base, factor, original_max_position, beta_fast,
                     beta_slow, attention_factor=None):
    """HF ``_compute_yarn_parameters`` (``truncate`` on), line by line
    with numpy float32 for torch float32."""
    def get_mscale(scale, mscale=1):
        if scale <= 1:
            return 1.0
        return 0.1 * mscale * math.log(scale) + 1.0

    if attention_factor is None:
        attention_factor = get_mscale(factor)

    def find_correction_dim(num_rotations, dim, base, max_pos):
        return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(base))

    def find_correction_range(low_rot, high_rot, dim, base, max_pos):
        low = math.floor(find_correction_dim(low_rot, dim, base, max_pos))
        high = math.ceil(find_correction_dim(high_rot, dim, base, max_pos))
        return max(low, 0), min(high, dim - 1)

    def linear_ramp_factor(min_, max_, dim):
        if min_ == max_:
            max_ += 0.001
        linear = (np.arange(dim, dtype=np.float32) - min_) / (max_ - min_)
        return np.clip(linear, 0, 1).astype(np.float32)

    pos_freqs = np.float32(base) ** (np.arange(0, dim, 2).astype(np.float32)
                                     / np.float32(dim))
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (np.float32(factor) * pos_freqs)
    low, high = find_correction_range(beta_fast, beta_slow, dim, base,
                                      original_max_position)
    extrapolation_factor = 1 - linear_ramp_factor(low, high, dim // 2)
    inv_freq = (inv_freq_interpolation * (1 - extrapolation_factor)
                + inv_freq_extrapolation * extrapolation_factor)
    return inv_freq.astype(np.float32), attention_factor


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, inv_freq, scale):
    """x (S, heads, hd); HF's rotate_half with cos/sin = cat(f, f)."""
    S = x.shape[0]
    ang = np.arange(S, dtype=np.float32)[:, None] * inv_freq[None]
    emb = np.concatenate([ang, ang], -1)
    cos = jnp.cos(emb)[:, None] * scale
    sin = jnp.sin(emb)[:, None] * scale
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(cfg, kind, p, x):
    S = x.shape[0]
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    h = _rms(x, p["ln"], cfg.norm_eps)
    q = jnp.einsum("sd,dhk->shk", h, p["wq"])
    k = jnp.einsum("sd,dhk->shk", h, p["wk"])
    v = jnp.einsum("sd,dhk->shk", h, p["wv"])
    if kind == "attn" and cfg.rope_yarn is not None:
        y = cfg.rope_yarn
        inv_freq, scale = hf_yarn_inv_freq(
            hd, cfg.rope_theta, y.factor, y.original_max_position,
            y.beta_fast, y.beta_slow, y.attention_factor)
    else:
        inv_freq = 1.0 / (np.float32(cfg.rope_theta) ** (
            np.arange(0, hd, 2, dtype=np.float32) / np.float32(hd)))
        scale = 1.0
    q, k = _rope(q, inv_freq, scale), _rope(k, inv_freq, scale)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    i = np.arange(S)[:, None]
    j = np.arange(S)[None, :]
    allowed = j <= i
    window = (cfg.sparse_attn_window if kind == "sattn"
              else cfg.sliding_window)
    if window is not None:
        near = i - j < window
        if kind == "sattn":
            near |= j < cfg.sparse_attn_global
        allowed &= near
    z = jnp.einsum("qhk,shk->hqs", q, k) * hd ** -0.5
    z = jnp.where(allowed[None], z, -jnp.inf)
    o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(z, -1), v)
    return x + jnp.einsum("qhk,hkd->qd", o, p["wo"])


def _swiglu(h, wg, wu, wd):
    return (jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _moe(cfg, p, x):
    h = _rms(x, p["ln"], cfg.norm_eps)
    probs = jax.nn.softmax(h @ p["router"], -1)             # (S, E)
    top, ids = jax.lax.top_k(probs, cfg.top_k)
    top = top / jnp.sum(top, -1, keepdims=True)
    first = 0 if cfg.experts_held is None else cfg.experts_held[0]
    out = jnp.zeros_like(x)
    for e in range(p["w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(ids == first + e, top, 0.0), -1)
        out += gate[:, None] * _swiglu(h, p["w_gate"][e], p["w_up"][e],
                                       p["w_down"][e])
    return x + out


def _dense_ffn(cfg, p, x):
    h = _rms(x, p["ln"], cfg.norm_eps)
    return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])


def forward(cfg, params, tokens):
    """Logits (B, S, V) of ``tokens`` (B, S)."""
    with jax.default_matmul_precision("highest"):
        outs = []
        for row in np.asarray(tokens):
            x = params["embed"][row].astype(jnp.float32)
            for per in range(cfg.num_periods):
                for i, kind in enumerate(cfg.pattern):
                    slot = jax.tree.map(lambda a: a[per],
                                        params["period"][f"slot{i}"])
                    x = _attention(cfg, kind, slot[kind], x)
                    if "ffn_moe" in slot:
                        x = _moe(cfg, slot["ffn_moe"], x)
                    elif "ffn_dense" in slot:
                        x = _dense_ffn(cfg, slot["ffn_dense"], x)
            x = _rms(x, params["final_norm"], cfg.norm_eps)
            outs.append(x @ params["lm_head"])
        return jnp.stack(outs)
