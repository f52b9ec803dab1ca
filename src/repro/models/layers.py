"""Core transformer layers: norms, RoPE, GQA attention (QKV bias,
qk_norm, sliding window, cross-attention), SwiGLU MLP.

All functions are pure; params are plain dicts of arrays.  Compute dtype
is the array dtype (bf16 in production configs); softmax/norm statistics
are always f32.  Attention is query-chunked (flash-style memory
behaviour without a handwritten kernel) so the (S x S) score matrix is
never materialized — required for the 32k prefill cells to fit HBM.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_freqs(head_dim: int, theta: float, yarn):
    """YaRN inverse frequencies and the factor cos and sin are scaled
    by, as Hugging Face's ``_compute_yarn_parameters`` computes them
    (float32, ``truncate`` on): dimensions that turn fewer than
    ``beta_slow`` times over ``original_max_position`` positions are
    interpolated by ``factor``, those that turn more than ``beta_fast``
    times are kept, and a linear ramp blends the ones between."""
    dim = head_dim

    def correction_dim(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = np.float32(theta) ** (
        np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    extrapolation = np.float32(1.0) / pos_freqs
    interpolation = np.float32(1.0) / (np.float32(yarn.factor) * pos_freqs)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - np.float32(low))
                   / np.float32(high - low), 0, 1).astype(np.float32)
    keep = np.float32(1.0) - ramp
    inv_freq = interpolation * (1 - keep) + extrapolation * keep
    scale = (yarn.attention_factor if yarn.attention_factor is not None
             else (0.1 * math.log(yarn.factor) + 1.0
                   if yarn.factor > 1 else 1.0))
    return inv_freq.astype(np.float32), float(scale)


def apply_rope(x, positions, theta: float, yarn=None):
    """x (..., S, H, hd); positions (..., S) int32.  ``yarn`` (a
    ``configs.base.YarnRope``) switches to YaRN's frequencies and
    scales cos and sin by its attention factor."""
    hd = x.shape[-1]
    if yarn is None:
        freqs, scale = rope_freqs(hd, theta), None       # (hd/2,)
    else:
        freqs, scale = yarn_freqs(hd, theta, yarn)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    cos = cos[..., None, :]                             # (..., S, 1, hd/2)
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core (query-chunked, GQA, causal / windowed / cross)
# ---------------------------------------------------------------------------

def _attend(q, k, v, mask):
    """q (B,Sq,KV,G,hd), k/v (B,Sk,KV,hd), mask (B|1,Sq,Sk) bool or None."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out


def gqa_attention(q, k, v, *, q_positions, kv_positions, causal: bool,
                  window: Optional[int] = None, num_global: int = 0,
                  chunk_q: int = 512, unroll_chunks: bool = False):
    """Grouped-query attention.

    q (B,Sq,H,hd), k/v (B,Sk,KV,hd).  H % KV == 0; G = H // KV.
    Causal/window masks are built from explicit positions so the same
    code serves training (positions 0..S) and decode (one new position
    against a cache).  ``num_global`` widens the window mask with
    longformer-style global key columns (positions < num_global stay
    visible to every later query) — the dense fallback for the sparse-
    attention ("sattn") serving paths; still ANDed with the causal
    test, so unfilled cache slots (UNFILLED_POS = +2^30) stay masked.
    Query-chunked via lax.map when Sq > chunk_q.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)

    def mask_for(qpos):
        m = None
        if causal:
            m = qpos[:, :, None] >= kv_positions[:, None, :]
        if window is not None:
            wm = qpos[:, :, None] - kv_positions[:, None, :] < window
            if num_global:
                wm |= kv_positions[:, None, :] < num_global
            m = wm if m is None else (m & wm)
        return m

    if Sq <= chunk_q:
        out = _attend(qg, k, v, mask_for(q_positions))
        return out.reshape(B, Sq, H, hd)

    assert Sq % chunk_q == 0, (Sq, chunk_q)
    nchunks = Sq // chunk_q
    qg_c = qg.reshape(B, nchunks, chunk_q, KV, G, hd)
    qpos_c = q_positions.reshape(B, nchunks, chunk_q)

    def one_chunk(args):
        qc, qp = args
        return _attend(qc, k, v, mask_for(qp))

    if unroll_chunks:
        # python-unrolled variant: loop-free HLO (used by the dry-run
        # cost probes, and by causal_skip below)
        outs = [one_chunk((qg_c[:, i], qpos_c[:, i]))
                for i in range(nchunks)]
        out = jnp.concatenate(outs, axis=1).reshape(B, Sq, H, hd)
        return out
    # scan over query chunks: peak memory O(B*H*chunk_q*Sk)
    out = jax.lax.map(one_chunk, (qg_c.swapaxes(0, 1), qpos_c.swapaxes(0, 1)))
    out = out.swapaxes(0, 1).reshape(B, Sq, H, hd)
    return out


def gqa_attention_causal_skip(q, k, v, *, q_positions, kv_positions,
                              window: Optional[int] = None,
                              chunk_q: int = 512):
    """Causal chunked attention with static block skipping.

    Flash-attention's causal trick at the HLO level: query chunk i only
    attends kv[0 : (i+1)*chunk_q] (positions are the standard aligned
    0..S layout), so fully-masked score blocks are never computed —
    ~2x fewer attention FLOPs, and with a sliding window the kv range
    is [lo_i, hi_i) with lo_i = max(0, hi_i - window - chunk_q):
    attention cost becomes O(S*window) instead of O(S^2).
    Bounds are python-static per chunk (unrolled), so the saving is
    real in the lowered HLO, not a mask.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    if Sq <= chunk_q:
        m = q_positions[:, :, None] >= kv_positions[:, None, :]
        if window is not None:
            m &= q_positions[:, :, None] - kv_positions[:, None, :] < window
        return _attend(qg, k, v, m).reshape(B, Sq, H, hd)
    assert Sq % chunk_q == 0
    nchunks = Sq // chunk_q
    outs = []
    for i in range(nchunks):
        hi = (i + 1) * chunk_q
        lo = 0 if window is None else max(0, hi - window - chunk_q)
        qc = qg[:, i * chunk_q: hi]
        qp = q_positions[:, i * chunk_q: hi]
        kc, vc = k[:, lo:hi], v[:, lo:hi]
        kp = kv_positions[:, lo:hi]
        m = qp[:, :, None] >= kp[:, None, :]
        if window is not None:
            m &= qp[:, :, None] - kp[:, None, :] < window
        outs.append(_attend(qc, kc, vc, m))
    return jnp.concatenate(outs, axis=1).reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# Attention layer (projections + rope + attend)
# ---------------------------------------------------------------------------

def attn_project_qkv(p, x, cfg_heads, cfg_kv_heads, head_dim, *, qk_norm,
                     norm_eps):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if qk_norm:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k = rms_norm(k, p["k_norm"], norm_eps)
    return q, k, v


def self_attention_layer(p, x, *, positions, head_dim, num_heads,
                         num_kv_heads, rope_theta, rope_yarn=None,
                         causal=True, window=None, qk_norm=False,
                         norm_eps=1e-5,
                         kv_override=None, chunk_q: int = 512,
                         unroll_chunks: bool = False,
                         causal_skip: bool = False):
    """Pre-norm self-attention block: x + attn(norm(x)).

    kv_override: (k, v, kv_positions) for decode-with-cache paths.
    """
    h = rms_norm(x, p["ln"], norm_eps)
    q, k, v = attn_project_qkv(p, h, num_heads, num_kv_heads, head_dim,
                               qk_norm=qk_norm, norm_eps=norm_eps)
    q = apply_rope(q, positions, rope_theta, rope_yarn)
    if kv_override is None:
        k = apply_rope(k, positions, rope_theta, rope_yarn)
        kv_positions = positions
    else:
        k, v, kv_positions = kv_override(k, v)
    if causal_skip and causal and kv_override is None:
        out = gqa_attention_causal_skip(
            q, k, v, q_positions=positions, kv_positions=kv_positions,
            window=window, chunk_q=chunk_q)
    else:
        out = gqa_attention(q, k, v, q_positions=positions,
                            kv_positions=kv_positions, causal=causal,
                            window=window, chunk_q=chunk_q,
                            unroll_chunks=unroll_chunks)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return x + out


def cross_attention_layer(p, x, kv_src, *, head_dim, num_heads,
                          num_kv_heads, qk_norm=False, norm_eps=1e-5,
                          chunk_q: int = 512, unroll_chunks: bool = False):
    """Cross-attention block (llama-3.2-vision image layers): queries from
    the text stream, keys/values from image embeddings; no causal mask,
    no RoPE; gated residual (tanh gate, init 0) as in llama-3.2."""
    h = rms_norm(x, p["ln"], norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].astype(x.dtype))
    kv = rms_norm(kv_src, p["ln_kv"], norm_eps)
    k = jnp.einsum("bsd,dhk->bshk", kv, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv, p["wv"].astype(x.dtype))
    if qk_norm:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k = rms_norm(k, p["k_norm"], norm_eps)
    B, Sq = q.shape[:2]
    Sk = k.shape[1]
    qpos = jnp.zeros((B, Sq), jnp.int32)
    kpos = jnp.zeros((B, Sk), jnp.int32)
    out = gqa_attention(q, k, v, q_positions=qpos, kv_positions=kpos,
                        causal=False, chunk_q=chunk_q,
                        unroll_chunks=unroll_chunks)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    gate = jnp.tanh(p["gate"].astype(jnp.float32)).astype(x.dtype)
    return x + gate * out


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def swiglu_mlp(p, x, *, norm_eps=1e-5):
    """Pre-norm SwiGLU FFN block: x + W_down(silu(W_gate h) * W_up h)."""
    h = rms_norm(x, p["ln"], norm_eps)
    g = jnp.einsum("bsd,df->bsf", h, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", h, p["w_up"].astype(x.dtype))
    act = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    out = jnp.einsum("bsf,fd->bsd", act, p["w_down"].astype(x.dtype))
    return x + out
