"""Mixture-of-Experts FFN layer.

Dispatch/combine use the gather/scatter form of the JIT-planned SpMM
(``core.moe_spmm``): the routing matrix S is applied as Sᵀ·tokens /
S·expert_out with static shapes, which is the in-jit realization of the
paper's technique (DESIGN.md §4.4); tests assert it matches the
concrete-routing Pallas path on identical routings.

Routing is grouped per batch row (standard local-dispatch-group
practice) so the dispatch buffer shards over the data axis:
buffer (B, E, C, D) with B→dp, E→ep (when divisible) — the
expert-capacity imbalance that motivates the paper's nnz_split.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..core import moe_spmm
from .layers import rms_norm


def _c(x, shard_ctx, spec):
    """Pin MoE buffers to batch-sharded layout: the vmapped dispatch
    scatter otherwise makes GSPMD replicate the FULL global batch on
    every chip (observed: (256, E*(C+1), D/16) f32 all-gathers)."""
    if shard_ctx is None or not shard_ctx.get("moe_shard"):
        return x
    from .transformer import _constrain
    return _constrain(x, shard_ctx, spec)


def moe_capacity(seq: int, top_k: int, num_experts: int,
                 capacity_factor=1.25) -> int:
    """Slots per expert in one routing group of ``seq`` tokens.  A
    token picks an expert at most once, so ``capacity_factor=None``
    (dropless) gives every expert ``seq`` slots: none overflows."""
    if capacity_factor is None:
        return seq
    return max(top_k, int(capacity_factor * seq * top_k / num_experts))


def moe_ffn(p: Dict, x: jax.Array, *, num_experts: int, top_k: int,
            capacity_factor=1.25, experts_held=None,
            norm_eps: float = 1e-5,
            shard_ctx=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Pre-norm MoE SwiGLU FFN: x + combine(experts(dispatch(norm(x)))).

    p: router (D,E), w_gate/w_up (n,D,F), w_down (n,F,D), ln (D,)
    x: (B, S, D).  Returns (out, aux_losses).

    The router scores all ``num_experts`` (E) experts; a token goes to
    its top_k, gates renormalised over them.  ``experts_held`` =
    (first, n) says this chip holds experts first .. first+n-1 (the
    weights' leading axis; None: all E): the layer adds their part of
    the result alone, and what the absent experts would add is left
    out, so the parts of all the chips sum to the whole layer.
    ``capacity_factor`` None routes dropless.
    """
    B, S, D = x.shape
    first, n_held = (0, num_experts) if experts_held is None \
        else experts_held
    h = rms_norm(x, p["ln"], norm_eps)
    with jax.named_scope("moe.route"):
        logits = jnp.einsum("bsd,de->bse", h.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
        C = moe_capacity(S, top_k, num_experts, capacity_factor)

        route = jax.vmap(lambda lg: moe_spmm.topk_routing(lg, top_k, C))
        gates, expert_ids, slots = route(logits)        # (B,S,k) each
        # renormalize gates over the chosen k (mixtral-style)
        gates = gates / jnp.clip(jnp.sum(gates, -1, keepdims=True), 1e-9)
        # pairs routed to experts held elsewhere take the dropped slot
        local = expert_ids - first
        held = (local >= 0) & (local < n_held)
        local = jnp.where(held, local, 0)
        slots = jnp.where(held, slots, C)

        disp = jax.vmap(
            lambda t, e, s: moe_spmm.dispatch(t, e, s, n_held, C))
        xe = disp(h, local, slots)                      # (B,n,C,D)
        xe = _c(xe, shard_ctx, ("DP", "model", None, None))

    with jax.named_scope("moe.experts"):
        g = jnp.einsum("becd,edf->becf", xe, p["w_gate"].astype(xe.dtype))
        u = jnp.einsum("becd,edf->becf", xe, p["w_up"].astype(xe.dtype))
        act = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
        oe = jnp.einsum("becf,efd->becd", act,
                        p["w_down"].astype(xe.dtype))
        oe = _c(oe, shard_ctx, ("DP", "model", None, None))

    with jax.named_scope("moe.combine"):
        comb = jax.vmap(moe_spmm.combine)
        out = comb(oe, gates.astype(oe.dtype), local, slots)  # (B,S,D)
        out = _c(out, shard_ctx, ("DP", None, None))

    # aux losses: switch load-balance + router z-loss
    probs = jax.nn.softmax(logits, axis=-1)             # (B,S,E)
    me = jnp.mean(probs, axis=(0, 1))                   # (E,)
    top1 = jax.nn.one_hot(jnp.argmax(logits, -1), num_experts)
    ce = jnp.mean(top1, axis=(0, 1))
    lb_loss = num_experts * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss}
    return x + out.astype(x.dtype), aux
