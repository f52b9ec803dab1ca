"""Wire-level compressed collectives via shard_map.

``compressed_psum`` implements the int8 gradient all-reduce the
jit-level transform in optim/compression.py cannot express (XLA places
GSPMD's all-reduce wherever it likes; here WE own the wire format):

  1. each participant quantizes its local shard contribution to int8
     with a per-tensor scale,
  2. the int8 payload + f32 scale are all-gathered (4x fewer bytes than
     an f32 ring all-reduce for the payload),
  3. each participant dequantizes-and-sums locally.

With error feedback at the call site (optim/compression.py) the
quantization error stays bounded across steps.  For the multi-pod mesh
this is applied on the "pod" (DCN) axis where bandwidth is scarcest.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _quantize(g):
    scale = jnp.max(jnp.abs(g)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def compressed_psum(x: jax.Array, mesh: Mesh, axis: str = "data"):
    """All-reduce `x` (replicated-shape per participant) over `axis`
    with an int8 wire format.  Returns the f32 sum."""

    def local(xl):
        q, scale = _quantize(xl.astype(jnp.float32))
        # wire: int8 payload + f32 scale, gathered across the axis
        qs = jax.lax.all_gather(q, axis)              # (n, ...) int8
        ss = jax.lax.all_gather(scale, axis)          # (n,) f32
        deq = qs.astype(jnp.float32) * ss.reshape(
            (-1,) + (1,) * (qs.ndim - 1))
        return jnp.sum(deq, axis=0)

    specs = P(*([None] * x.ndim))
    return jax.shard_map(local, mesh=mesh, in_specs=specs,
                         out_specs=specs, check_vma=False)(x)


def exact_panel_exchange(own: jax.Array, send_tbl: jax.Array,
                         recv_sel: jax.Array, axis: str) -> jax.Array:
    """Per-chip body of the plan-time exact-panel X exchange
    (DESIGN.md §7.8) — runs INSIDE a shard_map over ``axis``.

    Each chip owns a contiguous strip of bk-row X panels; the planner
    (``build_sharded_workspace(x_sharding="rows")``) knows exactly which
    panels each chip's descriptor stream touches and emits the send/recv
    schedule — the collective analogue of the paper's "load exactly the
    operands the instance needs", instead of replicating all of X per
    chip.  The schedule is rectangular for shard_map: every (src, dst)
    pair pads to the global max pairwise panel count T2, so under
    pairwise skew the wire carries up to C·T2 panels per chip rather
    than the exact touched set (see the DESIGN.md §7.8 padding note).

    own      : (P, bk, d) this chip's owned panel strip
    send_tbl : (C, T2) int32 — own-local panel ids to send each chip
    recv_sel : (T,) int32 — flat (C*T2,) receive-buffer index of each
               local panel, in the chip's fetch order
    returns  : (T*bk, d) the chip's compact local X workspace, rows laid
               out exactly as the remapped column stream addresses them
    """
    send = own[send_tbl]                          # (C, T2, bk, d)
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
    flat = recv.reshape((-1,) + recv.shape[2:])   # (C*T2, bk, d)
    panels = flat[recv_sel]                       # (T, bk, d)
    return panels.reshape(panels.shape[0] * panels.shape[1],
                          panels.shape[2])


def wire_bytes_ratio(shape: Tuple[int, ...]) -> float:
    """f32 ring-AR payload vs int8 all-gather payload per participant."""
    import numpy as np
    n = float(np.prod(shape))
    f32_ar = 2 * n * 4          # reduce-scatter + all-gather halves
    int8_ag = n * 1 + 4
    return f32_ar / int8_ag
