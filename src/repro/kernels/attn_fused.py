"""Fused sparse-attention sandwich — SDDMM → masked softmax → SpMM in
ONE dispatch through the descriptor stream.

The paper's claim is that runtime knowledge of the sparsity pattern
lets one generated kernel beat AOT pipelines; sparse attention is the
strongest test because the SAME plan must drive three chained
contractions.  An AOT pipeline runs them as three dispatches with the
score matrix ``S = mask ⊙ (Q·Kᵀ)`` round-tripping through HBM twice;
here each descriptor trip computes its scores as a sampled dense-dense
product (SDDMM) over its slots, folds them into a running softmax held in the
vector register file, and immediately consumes ``S·V`` through the
existing ELL/BCSR trip machinery — ``S`` never materializes
(DESIGN.md §13).

Per grid step the descriptor is read from SMEM exactly as in the SpMM
kernel (``spmm_bcsr_fused``); the only new state is
the online-softmax carry per sub-block row: accumulator ``acc`` plus
running max ``m`` and running denominator ``l``.  Each trip rescales
the carry by ``exp(m - m_new)`` before folding its contribution, so a
block-row whose nonzeros span many trips (multi-trip rows) gets the
EXACT softmax — the rescale telescopes to a single global max.  The
mask weight ``w`` rides in the shared ``vals_flat`` slot stream (zero
on padding slots), giving the semantics

    out[i] = sum_j p_ij V[j],   p_ij = w_ij exp(z_ij) / sum_k w_ik exp(z_ik)

i.e. ``softmax(z + log w)`` over the present entries — plain masked
softmax when the weights are 1.  Padding slots are killed NaN-free by
the clamp form ``p = w · exp(min(z - m_new, 0))``: when ``w > 0`` the
running max already dominates ``z`` so the clamp is inactive; when
``w == 0`` it stops ``0 · exp(+inf)``.

Operand staging matches the SpMM kernels: ``resident`` scalar-prefetches
the column and weight streams into SMEM; ``dma`` (``attn_fused_staged``)
double-buffers each trip's windows of them into SMEM (VPU weights) or
VMEM (MXU weight panels) rings.  Q/K/V stay resident BlockSpec panels
in both modes (the row gather touches arbitrary key rows).  A block
split into piece trips carries its softmax state ``(acc, m, l)`` from
trip to trip in VMEM, so the result is the unsplit one.
``attn_fused_sharded`` runs the same kernel once per chip under
``shard_map``: descriptor tables and the workspace-ordered Q stacked
per chip, K/V replicated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..platform import LANE
from . import staging as st

# finite "masked" score: matches models/layers.py NEG_INF; keeping it
# finite (not -inf) makes the m == m_new warmup rescale exp(0) exact
_NEG = -1e30
_VPU = 0
_HIGHEST = jax.lax.Precision.HIGHEST


def _softmax_trip(acc, m, l, z, w, vg):
    """Fold one trip's scores into the online-softmax carry.

    acc (bm, dt) weighted-V accumulator, m (bm, 1) running max, l (bm, 1)
    running denominator; z (bm, k) trip scores, w (bm, k) mask weights
    (0 on padding), vg (k, dt) the trip's V rows.  Exact across trips:
    the exp(m - m_new) rescale telescopes to one global max.
    """
    zm = jnp.where(w > 0, z, _NEG)
    m_new = jnp.maximum(m, jnp.max(zm, axis=1, keepdims=True))
    r = jnp.exp(m - m_new)
    # clamp keeps padding slots NaN-free: w == 0 kills the term and the
    # min() stops exp overflowing; w > 0 implies z <= m_new so the
    # clamp never alters a live score
    p = w * jnp.exp(jnp.minimum(z - m_new, 0.0))
    acc = acc * r + jax.lax.dot_general(
        p, vg, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST)
    l = l * r + jnp.sum(p, axis=1, keepdims=True)
    return acc, m_new, l


def _scores(q_blk, kp):
    """(bm, dh)·(bk, dh)ᵀ block scores on the MXU."""
    return jax.lax.dot_general(
        q_blk, kp, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HIGHEST)


def _member(w, *, tag, L, cont, carries, j, q_ref, k_ref, v_ref,
            col, weight, weight_panel, bm: int, bk: int, dt: int,
            vpu: bool):
    """One member descriptor of a merged trip: its own tag dispatch and
    its own ``(acc, m, l)`` softmax carry, started from the previous
    trip's on a piece trip.  ``col(i)``/``weight(i)`` read the i-th
    entry of the member's column/weight stream, ``weight_panel(k)`` its
    k-th MXU weight panel.  ``vpu`` False (a stream of MXU trips only)
    lowers the MXU trip alone: no branch on the tag and no VPU trip,
    whose ``bm`` single-row K/V loads are unrolled."""
    cacc, cm, cl = carries
    rows = pl.ds(w * bm, bm)
    q_blk = q_ref[rows, :].astype(jnp.float32)
    carry0 = jax.lax.cond(
        cont, lambda: (cacc[j, rows, :], cm[j, rows, :], cl[j, rows, :]),
        lambda: (jnp.zeros((bm, dt), jnp.float32),
                 jnp.full((bm, 1), _NEG, jnp.float32),
                 jnp.zeros((bm, 1), jnp.float32)))

    def vpu_block():
        # SDDMM one column at a time: gather the bm K/V rows the trip's
        # slots name, score against the resident Q block
        def nnz_step(nz, carry):
            acc, m, l = carry
            cs = [col(rr * L + nz) for rr in range(bm)]
            kg = jnp.concatenate([k_ref[pl.ds(c, 1), :] for c in cs],
                                 axis=0).astype(jnp.float32)
            vg = jnp.concatenate([v_ref[pl.ds(c, 1), :] for c in cs],
                                 axis=0).astype(jnp.float32)
            wv = st.column([weight(rr * L + nz) for rr in range(bm)])
            z = jnp.sum(q_blk * kg, axis=1, keepdims=True)
            zm = jnp.where(wv > 0, z, _NEG)
            m_new = jnp.maximum(m, zm)
            r = jnp.exp(m - m_new)
            p = wv * jnp.exp(jnp.minimum(z - m_new, 0.0))
            return acc * r + p * vg, m_new, l * r + p
        return jax.lax.fori_loop(0, L, nnz_step, carry0)

    def mxu_block():
        # SDDMM a block-column at a time: (bm, dh)·(bk, dh)ᵀ scores on
        # the MXU, then the (bm, bk)·(bk, dt) S·V panel matmul
        def blk_step(kk, carry):
            bc = col(kk)
            kp = k_ref[pl.ds(pl.multiple_of(bc * bk, bk), bk), :]
            vp = v_ref[pl.ds(pl.multiple_of(bc * bk, bk), bk), :]
            return _softmax_trip(*carry, _scores(q_blk, kp.astype(
                jnp.float32)), weight_panel(kk), vp.astype(jnp.float32))
        return jax.lax.fori_loop(0, L, blk_step, carry0)

    if not vpu:
        return mxu_block()
    return jax.lax.cond(tag == _VPU, vpu_block, mxu_block)


def _finish(results, carries, j, y_ref, mw: int):
    """Keep the raw softmax state for a following piece trip and store
    the normalized rows (all-padding rows keep l == 0 and normalize to
    zero)."""
    cacc, cm, cl = carries
    cat = (lambda xs: xs[0] if mw == 1 else jnp.concatenate(xs, axis=0))
    acc, m, l = (cat([r[i] for r in results]) for i in range(3))
    cacc[j], cm[j], cl[j] = acc, m, l
    y_ref[...] = (acc / jnp.where(l > 0, l, 1.0)).astype(y_ref.dtype)


def _kernel(tag_ref, off_ref, coff_ref, L_ref, cont_ref, cols_ref,
            vals_s_ref, vals_ref, q_ref, k_ref, v_ref, y_ref, cacc, cm, cl,
            *, bm: int, bk: int, dt: int, mw: int, vpu: bool):
    g = pl.program_id(0)
    j = pl.program_id(1)
    carries = (cacc, cm, cl)

    def member(w):
        d = g * mw + w
        off, coff = off_ref[d], coff_ref[d]
        return _member(
            w, tag=tag_ref[d], L=L_ref[d], cont=cont_ref[g] > 0,
            carries=carries, j=j, q_ref=q_ref, k_ref=k_ref, v_ref=v_ref,
            col=lambda i: cols_ref[coff + i],
            weight=lambda i: vals_s_ref[off + i],
            weight_panel=lambda k: st.panel_at(vals_ref, off // LANE
                                               + k * bm, bm, bk),
            bm=bm, bk=bk, dt=dt, vpu=vpu)

    _finish([member(w) for w in range(mw)], carries, j, y_ref, mw)


def _staged_kernel(tag_ref, off_ref, coff_ref, L_ref, cont_ref, cols_ref,
                   vals_ref, q_ref, k_ref, v_ref, y_ref, cring, vring,
                   mring, cacc, cm, cl, csem, vsem, *, bm: int, bk: int,
                   dt: int, span: int, cspan: int, mw: int, vpu: bool):
    """Double-buffered twin of :func:`_kernel` (DESIGN.md §7.7/§13):
    the same trip windows and rings as the staged SpMM kernel; Q/K/V
    stay resident BlockSpec panels.  Without VPU trips the SMEM value
    ring is one tile that no copy fills."""
    g = pl.program_id(0)
    j = pl.program_id(1)
    ng = pl.num_programs(0)
    carries = (cacc, cm, cl)
    st.stage_trip_windows(tag_ref, off_ref, coff_ref, cols_ref, vals_ref,
                          cring, vring, mring, csem, vsem, g=g, j=j, ng=ng,
                          mw=mw, vrows=span // LANE, crows=cspan // LANE,
                          vpu=vpu)
    slot = g % 2
    vbase = st.window_base(off_ref[g * mw]) * LANE
    cbase = st.window_base(coff_ref[g * mw]) * LANE

    def member(w):
        d = g * mw + w
        loff, lcoff = off_ref[d] - vbase, coff_ref[d] - cbase
        return _member(
            w, tag=tag_ref[d], L=L_ref[d], cont=cont_ref[g] > 0,
            carries=carries, j=j, q_ref=q_ref, k_ref=k_ref, v_ref=v_ref,
            col=lambda i: st.read(cring, slot, lcoff + i),
            weight=lambda i: st.read(vring, slot, loff + i),
            weight_panel=lambda k: st.panel(mring, slot, loff // LANE
                                            + k * bm, bm, bk),
            bm=bm, bk=bk, dt=dt, vpu=vpu)

    _finish([member(w) for w in range(mw)], carries, j, y_ref, mw)


def _carry_scratch(nj: int, rows: int, dt: int):
    return [pltpu.VMEM((nj, rows, dt), jnp.float32),
            pltpu.VMEM((nj, rows, 1), jnp.float32),
            pltpu.VMEM((nj, rows, 1), jnp.float32)]


def _prepare(blk_L, k, v, cont, bk: int, mw: int):
    if cont is None:
        cont = jnp.zeros((blk_L.shape[0] // mw,), jnp.int32)
    pad = -k.shape[0] % max(bk, 8)
    if pad:
        k = jnp.pad(k, ((0, pad), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0)))
    return k, v, cont


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "mw", "vpu", "interpret"))
def attn_fused(blk_tag: jax.Array, blk_off: jax.Array,
               blk_coff: jax.Array, blk_L: jax.Array,
               cols_flat: jax.Array, vals_flat: jax.Array,
               q_ws: jax.Array, k: jax.Array, v: jax.Array, cont=None, *,
               bm: int = 8, bk: int = 8, mw: int = 1, vpu: bool = True,
               interpret: bool = True) -> jax.Array:
    """Compute the WHOLE sparse-attention plan in one dispatch:
    Y_ws (ws_rows, dv_pad) = softmax(mask ⊙ (Q·Kᵀ)) · V.

    blk_tag   : (B,) int32 — 0 = VPU ELL block, 1 = MXU block-row
    blk_off   : (B,) int32 — first slot of each block in vals_flat
    blk_coff  : (B,) int32 — first entry of each block in cols_flat
    blk_L     : (B,) int32 — trips: the block's longest row (VPU) or K
                (MXU)
    cols_flat : (Sc,) int32 — K/V row per slot (VPU) / block-col (MXU)
    vals_flat : (S,) float — mask weights per slot, zero on padding
    q_ws      : (B*bm, dh_pad) float — Q in WORKSPACE row order (the
                planner's ``workspace_row_map`` gather, scale folded
                in), head dim padded to the lane tile
    k         : (n, dh_pad) float
    v         : (n, dv_pad) float — value dim padded to the lane tile;
                dv tiles the second grid axis
    cont      : (B // mw,) int32 — piece trips (default: none)
    vpu       : whether ``blk_tag`` holds VPU blocks; False lowers the
                MXU trip alone

    Returns workspace-ordered rows; the caller applies the plan's
    ``inv_perm`` gather to recover output row order.
    """
    from ..core.ccm import kernel_lane_tile  # lazy: core imports kernels

    num_blocks = blk_tag.shape[0]
    assert num_blocks % mw == 0, (num_blocks, mw)
    k, v, cont = _prepare(blk_L, k, v, cont, bk, mw)
    vals2 = st.stream_rows(vals_flat.astype(jnp.float32))
    n_pad, dh_pad = k.shape
    dv_pad = v.shape[1]
    dt = kernel_lane_tile(dv_pad)
    nj = dv_pad // dt

    return pl.pallas_call(
        functools.partial(_kernel, bm=bm, bk=bk, dt=dt, mw=mw, vpu=vpu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(num_blocks // mw, nj),
            in_specs=[
                pl.BlockSpec(vals2.shape, lambda g, j, *_: (0, 0)),
                pl.BlockSpec((mw * bm, dh_pad), lambda g, j, *_: (g, 0)),
                pl.BlockSpec((n_pad, dh_pad), lambda g, j, *_: (0, 0)),
                pl.BlockSpec((n_pad, dt), lambda g, j, *_: (0, j)),
            ],
            out_specs=pl.BlockSpec((mw * bm, dt), lambda g, j, *_: (g, j)),
            scratch_shapes=_carry_scratch(nj, mw * bm, dt),
        ),
        out_shape=jax.ShapeDtypeStruct((num_blocks * bm, dv_pad),
                                       jnp.float32),
        interpret=interpret,
        name="attn_fused",
    )(blk_tag, blk_off, blk_coff, blk_L, cont, cols_flat,
      vals_flat.astype(jnp.float32), vals2, q_ws, k, v)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "mw", "vpu", "span", "cspan",
                     "interpret"))
def attn_fused_staged(blk_tag: jax.Array, blk_off: jax.Array,
                      blk_coff: jax.Array, blk_L: jax.Array,
                      cols_flat: jax.Array, vals_flat: jax.Array,
                      q_ws: jax.Array, k: jax.Array, v: jax.Array,
                      cont=None, *, span: int, cspan: int, bm: int = 8,
                      bk: int = 8, mw: int = 1, vpu: bool = True,
                      interpret: bool = True) -> jax.Array:
    """The DMA-staged fused attention dispatch — same contract as
    :func:`attn_fused` and BIT-identical output.  ``span``/``cspan``
    are the workspace's ``max_span``/``max_cspan`` per-merged-trip DMA
    windows over the slot/column streams (DESIGN.md §7.7)."""
    from ..core.ccm import kernel_lane_tile  # lazy: core imports kernels

    num_blocks = blk_tag.shape[0]
    assert num_blocks % mw == 0, (num_blocks, mw)
    if num_blocks > st.call_descs(mw):
        raise ValueError(
            f"{num_blocks} attention descriptors exceed the "
            f"{st.call_descs(mw)} one staged call holds in SMEM")
    k, v, cont = _prepare(blk_L, k, v, cont, bk, mw)
    n_pad, dh_pad = k.shape
    dv_pad = v.shape[1]
    dt = kernel_lane_tile(dv_pad)
    nj = dv_pad // dt
    vrows, crows = span // LANE, cspan // LANE

    return pl.pallas_call(
        functools.partial(_staged_kernel, bm=bm, bk=bk, dt=dt, span=span,
                          cspan=cspan, mw=mw, vpu=vpu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(num_blocks // mw, nj),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),     # cols (HBM)
                pl.BlockSpec(memory_space=pl.ANY),     # vals (HBM)
                pl.BlockSpec((mw * bm, dh_pad), lambda g, j, *_: (g, 0)),
                pl.BlockSpec((n_pad, dh_pad), lambda g, j, *_: (0, 0)),
                pl.BlockSpec((n_pad, dt), lambda g, j, *_: (0, j)),
            ],
            out_specs=pl.BlockSpec((mw * bm, dt), lambda g, j, *_: (g, j)),
            scratch_shapes=[
                pltpu.SMEM((2, crows, LANE), jnp.int32),     # cols
                pltpu.SMEM((2, vrows if vpu else st.TILE_ROWS, LANE),
                           jnp.float32),                     # VPU weights
                pltpu.VMEM((2, vrows, LANE), jnp.float32),   # MXU weights
                *_carry_scratch(nj, mw * bm, dt),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((num_blocks * bm, dv_pad),
                                       jnp.float32),
        interpret=interpret,
        name="attn_fused_staged",
    )(blk_tag, blk_off, blk_coff, blk_L, cont, st.stream_rows(cols_flat),
      st.stream_rows(vals_flat.astype(jnp.float32)), q_ws, k, v)


def attn_fused_sharded(blk_tag: jax.Array, blk_off: jax.Array,
                       blk_coff: jax.Array, blk_L: jax.Array,
                       cols_flat: jax.Array, vals_flat: jax.Array,
                       q_ws: jax.Array, k: jax.Array, v: jax.Array,
                       cont=None, *, mesh, bm: int = 8, bk: int = 8,
                       mw: int = 1, vpu: bool = True,
                       interpret: bool = True,
                       staging: str = "resident", span=0,
                       cspan=0) -> jax.Array:
    """Run one fused attention dispatch per chip under ``shard_map``.

    Descriptor tables and the workspace-ordered ``q_ws`` are (C, ...)
    stacked per chip (each chip's Q rows come from its own
    ``workspace_row_map`` shard); K and V are replicated — attention
    rows read arbitrary key columns, so the row-sharded X exchange of
    the SpMM path does not apply (``x_sharding`` is pinned
    ``"replicated"`` upstream).  Returns (C, B*bm, dv_pad) workspace
    rows sharded over the chip axis; the caller flattens and applies
    the sharded workspace's GLOBAL ``inv_perm`` gather.
    ``staging="dma"`` lowers each chip through
    :func:`attn_fused_staged`; ``span``/``cspan`` may be per-chip
    tuples (see ``staging.staged_dispatch``).
    """
    if cont is None:
        cont = jnp.zeros((blk_L.shape[0], blk_L.shape[1] // mw), jnp.int32)
    fn = _sharded_callable(mesh, bm, bk, interpret, staging,
                           st.chip_windows(span, mesh.size),
                           st.chip_windows(cspan, mesh.size), mw, vpu)
    return fn(blk_tag, blk_off, blk_coff, blk_L, cont, cols_flat,
              vals_flat, q_ws, k, v)


@functools.lru_cache(maxsize=32)
def _sharded_callable(mesh, bm: int, bk: int, interpret: bool,
                      staging: str = "resident", spans: tuple = (0,),
                      cspans: tuple = (0,), mw: int = 1,
                      vpu: bool = True):
    """jit-wrapped shard_map closure, memoized per (mesh, bm, bk,
    interpret, staging, spans, cspans, mw, vpu) — same lifecycle as the
    SpMM twin; evicted by ``core.jit_cache.clear_global_cache``."""
    (axis,) = mesh.axis_names

    if staging == "dma":
        def call(sp, cs):
            return functools.partial(attn_fused_staged, span=sp,
                                     cspan=cs, bm=bm, bk=bk, mw=mw,
                                     vpu=vpu, interpret=interpret)
        kernel = st.staged_dispatch(axis, spans, cspans, call)
    else:
        kernel = functools.partial(attn_fused, bm=bm, bk=bk, mw=mw,
                                   vpu=vpu, interpret=interpret)

    shard = P(axis)

    def per_chip(tag, off, coff, L, cont, cols, vals, q, kk, vv):
        return kernel(tag[0], off[0], coff[0], L[0], cols[0], vals[0],
                      q[0], kk, vv, cont[0])[None]

    return st.shard(per_chip, mesh, (shard,) * 8 + (P(), P()), shard)
