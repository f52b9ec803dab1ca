"""Plain jnp reference of a sliding/full-attention MoE decoder's
prefill logits (Mellum2's shape), independent of the program: float32,
every matrix product at precision ``highest``, no kernels, cache,
scan or capacity; one prompt and one layer after the other.

Per layer: RMSNorm; Q, K, V; RoPE by the layer's kind (plain on
sliding layers, YaRN on full ones, as Hugging Face's
``_compute_yarn_parameters`` and ``rotate_half``); causal attention,
on sliding layers over keys with ``i - j < sliding_window`` and on
full ones over all earlier keys, in blocks of queries so the scores of
a whole layer need not fit at once; the output projection and the
residual; RMSNorm; the router's softmax over all its outputs, top-k
and the gates renormalised over them (``norm_topk_prob``); the held
experts' SwiGLU, each weighted by its gate where it was chosen; the
residual.  Then the final RMSNorm and the head over the sliced
vocabulary.

Departures from Hugging Face's Mellum, all as the configuration file
states them: the held experts alone add to the MLP (the chip's share
of an expert-parallel layer), the vocabulary is a slice, one period of
layers, no multi-token-prediction head.

Routing ties.  Top-k routing is discontinuous: where a token's k-th
and (k+1)-th router logits lie closer together than two float32
computations of them differ (up to about 1e-4 at these widths, the
program's against this reference on the chip), either expert is a
right answer; the two picked differently in 3 of 29 prompts of
4,096 tokens on the chip.  The reference marks every token and layer
whose two logits lie within ``TIE_LOGIT`` of each other.  Where ``inputs`` holds the
program's logits (``program_logits``, the step's first output, a host
copy), the first row farther from them than the cell's limit is the
row of a tie resolved otherwise, if any: a tie changes its own row
and, through attention, only later rows, whose own ties may then turn
as well.  The reference takes that token's other choice at a marked
layer where this brings the row within the limit, computes the prompt
again, and goes on to the next such row, up to ``MAX_PASSES`` ties:
its answer is then the float32 forward that resolved those ties as
the program did.  Every row is still compared, and a row the program
got wrong where there is no tie stays wrong.

``precision="control"`` rounds both operands of every product to
bfloat16 (one MXU pass, as at ``Precision.DEFAULT``): the nearest step
below the float32 that the configuration states.  It resolves no ties.
"""
from __future__ import annotations

import functools
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
TIE_LOGIT = 1e-2    # a hundred times the widest router-logit gap measured
MAX_PASSES = 4     # ties resolved per prompt, at most


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _einsum(control: bool, spec: str, a, b):
    if control:
        a, b = _bf16(a), _bf16(b)
    return jnp.einsum(spec, a, b)


def yarn_inv_freq(dim, base, p):
    """HF ``_compute_yarn_parameters`` (``truncate`` on), float32."""
    factor = p["factor"]

    def correction_dim(rotations):
        return (dim * math.log(p["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(p["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(p["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = np.float32(base) ** (np.arange(0, dim, 2).astype(np.float32)
                                     / np.float32(dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1).astype(np.float32)
    extrapolation = 1 - ramp
    inv_freq = (1.0 / (np.float32(factor) * pos_freqs) * (1 - extrapolation)
                + 1.0 / pos_freqs * extrapolation)
    scale = p.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(scale)


def _rope_table(config: dict, layer_type: str, S: int):
    p = config["rope_parameters"][layer_type]
    dim = int(config["head_dim"])
    if p["rope_type"] == "yarn":
        inv_freq, scale = yarn_inv_freq(dim, p["rope_theta"], p)
    else:
        inv_freq = 1.0 / (np.float32(p["rope_theta"]) ** (
            np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)))
        scale = 1.0
    ang = np.arange(S, dtype=np.float32)[:, None] * inv_freq[None]
    emb = np.concatenate([ang, ang], -1)
    return np.cos(emb) * scale, np.sin(emb) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, cos, sin):
    x1, x2 = jnp.split(x, 2, -1)
    return x * cos[:, None] + jnp.concatenate([-x2, x1], -1) * sin[:, None]


@functools.partial(jax.jit, static_argnames=("window", "eps", "control"))
def _attention(x, p, cos, sin, *, window, eps, control):
    """One attention block over the whole prompt; ``window`` None is
    full causal attention."""
    S = x.shape[0]
    H, hd = p["wq"].shape[-2:]
    KV = p["wk"].shape[-2]
    h = _rms(x, p["attn_ln"], eps)
    q = _rope(_einsum(control, "sd,dhk->shk", h, p["wq"]), cos, sin)
    k = _rope(_einsum(control, "sd,dhk->shk", h, p["wk"]), cos, sin)
    v = _einsum(control, "sd,dhk->shk", h, p["wv"])
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    j = jnp.arange(S)[None, :]
    outs = []
    for lo in range(0, S, Q_BLOCK):
        i = jnp.arange(lo, min(lo + Q_BLOCK, S))[:, None]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        z = _einsum(control, "qhk,shk->hqs", q[lo:lo + Q_BLOCK], k) \
            * hd ** -0.5
        a = jax.nn.softmax(jnp.where(seen[None], z, -jnp.inf), -1)
        outs.append(_einsum(control, "hqs,shk->qhk", a, v))
    o = jnp.concatenate(outs)
    return x + _einsum(control, "qhk,hkd->qd", o, p["wo"])


@functools.partial(jax.jit, static_argnames=("top_k", "first", "tie",
                                             "eps", "control"))
def _moe(x, p, flip, *, top_k, first, tie, eps, control):
    """The MLP block; a token with ``flip`` set takes its (k+1)-th
    expert in place of its k-th.  Also returns, per token, whether its
    k-th and (k+1)-th router logits lie within ``tie``."""
    h = _rms(x, p["moe_ln"], eps)
    logits = _einsum(control, "sd,de->se", h, p["router"])
    probs = jax.nn.softmax(logits, -1)
    top, ids = jax.lax.top_k(probs, top_k + 1)
    ranked = jnp.take_along_axis(logits, ids, -1)
    near = ranked[:, top_k - 1] - ranked[:, top_k] < tie
    last = flip[:, None] & (jnp.arange(top_k) == top_k - 1)
    ids = jnp.where(last, ids[:, top_k:], ids[:, :top_k])
    top = jnp.where(last, top[:, top_k:], top[:, :top_k])
    top = top / jnp.sum(top, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(p["w_gate"].shape[0]):
        gate = jnp.sum(jnp.where(ids == first + e, top, 0.0), -1)
        g = _einsum(control, "sd,df->sf", h, p["w_gate"][e])
        u = _einsum(control, "sd,df->sf", h, p["w_up"][e])
        y = _einsum(control, "sf,fd->sd", jax.nn.silu(g) * u,
                    p["w_down"][e])
        out = out + gate[:, None] * y
    return x + out, near


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, norm, head, *, eps, control):
    return _einsum(control, "sd,dv->sv", _rms(x, norm, eps), head)


def _forward(config, inputs, row, flips, control):
    """Logits (S, V) of one prompt, and the (L, S) token-layers whose
    routing lies within ``TIE_LOGIT`` of a tie."""
    eps = float(config["rms_norm_eps"])
    L = int(config["num_hidden_layers"])
    S = int(row.shape[0])
    windows = {"sliding_attention": int(config["sliding_window"]),
               "full_attention": None}
    x = inputs["embed"][row]
    near = []
    for layer, (layer_type, slot) in enumerate(
            zip(config["layer_types"][:L], inputs["slots"])):
        p = {name: a[0] for name, a in slot.items()}
        cos, sin = _rope_table(config, layer_type, S)
        x = _attention(x, p, cos, sin, window=windows[layer_type],
                       eps=eps, control=control)
        x, n = _moe(x, p, jnp.asarray(flips[layer]),
                    top_k=int(config["num_experts_per_tok"]),
                    first=int(config["first_expert_held"]), tie=TIE_LOGIT,
                    eps=eps, control=control)
        near.append(np.asarray(n))
    logits = _head(x, inputs["final_norm"], inputs["lm_head"], eps=eps,
                   control=control)
    return logits, np.stack(near)


def _row_gaps(out, ref):
    """Each row's widest gap over its largest reference magnitude,
    floored at the median row's (the harness's comparison)."""
    scale = np.abs(ref).max(1)
    scale = np.maximum(scale, np.median(scale))
    return np.abs(out - ref).max(1) / np.where(scale > 0, scale, 1.0)


def compute(structure, config: dict, traffic: dict, inputs: dict,
            precision: str) -> dict:
    if precision not in ("reference", "control"):
        raise ValueError(precision)
    control = precision == "control"
    L = int(config["num_hidden_layers"])
    tokens = np.asarray(inputs["tokens"])
    B, S = tokens.shape
    program = inputs.get("program_logits")
    limit = float(traffic["limits"]["logits"])
    out = []
    with jax.default_matmul_precision("highest"):
        for b, row in enumerate(tokens):
            flips = np.zeros((L, S), bool)
            logits, near = _forward(config, inputs, row, flips, control)
            for _ in range(MAX_PASSES if program is not None
                           and not control else 0):
                mine = program[b * S:(b + 1) * S]
                over = _row_gaps(mine, np.asarray(logits)) > limit
                if not over.any():
                    break
                # a tie resolved otherwise changes its own row and, through
                # attention, later rows (whose own ties may then turn):
                # the first row over the limit is such a tie's own row
                tok = int(np.argmax(over))
                for layer in np.flatnonzero(near[:, tok]):
                    f = flips.copy()
                    f[layer, tok] = True
                    lg, nr = _forward(config, inputs, row, f, control)
                    if _row_gaps(mine, np.asarray(lg))[tok] <= limit:
                        flips, logits, near = f, lg, nr
                        break
                else:
                    break       # not a tie the program resolved otherwise
            print(f"[reference] prompt {b}: {int(near.sum())} token-layers "
                  f"within {TIE_LOGIT} of a routing tie, {int(flips.sum())}"
                  f" resolved as the program did", file=sys.stderr,
                  flush=True)
            out.append(logits)
    return {"logits": jnp.concatenate(out)}
