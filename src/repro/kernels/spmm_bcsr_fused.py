"""Mixed VPU/MXU fused SpMM kernel — every block-row of the plan, ELL
rows and BCSR block-rows alike, in one descriptor stream.

The planner's :class:`~repro.core.plan.MixedPlan` tags every
``bm``-aligned row-block with the execution unit that wins on its
structure, and ONE ``pallas_call`` covers both:

  VPU descriptor (tag 0): ``blk_L`` = the longest of the block's own
      rows, shorter rows padded to it; each trip gathers one X row per
      block row and scales it by that slot's value, read as a scalar
      from SMEM.  A plan tagged with ``mxu_gain=0`` is all VPU
      descriptors.
  MXU descriptor (tag 1): ``blk_L`` = the block-row's own ``K``; each
      trip multiplies one ``(bm, bk)`` value panel (an aligned vector
      tile: the packer lane-pads panels to ``(bm, LANE)``) against the
      ``(bk, dt)`` X panel of its block-column on the MXU, in full f32
      precision.

The tag is a scalar-prefetched SMEM read, so the branch is resolved in
the scalar unit per grid step (``lax.cond``); the grid itself is
static.

Two lowerings, bit-identical (DESIGN.md §7.7):

  resident  (:func:`spmm_bcsr_fused`) the column and value streams are
            scalar-prefetched into SMEM and X is a VMEM panel — the
            interpret-mode oracle and the path for instances whose
            streams fit the fast memories.
  dma       (:func:`spmm_bcsr_fused_staged`) every stream stays in HBM.
            Each trip's ``[off, off + span)`` window of the column and
            value streams is copied into a two-slot SMEM ring (an MXU
            trip's values into a VMEM ring) while the previous trip
            computes, and X is fetched per trip: ``bm`` row copies on a
            VPU trip, one ``(bk, dt)`` panel on an MXU trip, each one
            step ahead.  A stream whose descriptor tables exceed SMEM
            is issued as a sequence of calls (``staging.issue_in_calls``).

A block wider than the platform's window arrives from the planner as
consecutive piece trips; ``cont[t] == 1`` starts trip ``t``'s
accumulator from trip ``t - 1``'s (kept in a VMEM carry), so the
accumulation order is the unsplit one.

``spmm_bcsr_fused_sharded`` runs the same kernel once per chip under
``shard_map``: stacked per-chip descriptor tables on the leading axis,
X replicated or exchanged panel-exactly (DESIGN.md §7.8).
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

_VPU = 0
_HIGHEST = jax.lax.Precision.HIGHEST


def _mxu_dot(a, xp):
    return jnp.dot(a.astype(jnp.float32), xp.astype(jnp.float32),
                   preferred_element_type=jnp.float32, precision=_HIGHEST)


def _start_acc(cont, carry, j, w: int, bm: int, dt: int):
    """A member's initial accumulator: zeros, or on a piece trip the
    previous trip's rows."""
    return jax.lax.cond(cont, lambda: carry[j, pl.ds(w * bm, bm), :],
                        lambda: jnp.zeros((bm, dt), jnp.float32))


def _kernel(tag_ref, off_ref, coff_ref, L_ref, cont_ref, cols_ref,
            vals_s_ref, vals_ref, x_ref, y_ref, carry, *, bm: int,
            bk: int, dt: int, mw: int):
    g = pl.program_id(0)
    j = pl.program_id(1)
    cont = cont_ref[g] > 0

    def member(w):
        d = g * mw + w
        off, coff, L = off_ref[d], coff_ref[d], L_ref[d]
        acc0 = _start_acc(cont, carry, j, w, bm, dt)

        def vpu_block():
            # bm independent gather+FMA chains (static unroll == ILP)
            def nnz_step(nz, acc):
                xs = [x_ref[pl.ds(cols_ref[coff + rr * L + nz], 1), :]
                      for rr in range(bm)]
                v = st.column([vals_s_ref[off + rr * L + nz]
                               for rr in range(bm)])
                return acc + v * jnp.concatenate(xs, axis=0).astype(
                    jnp.float32)
            return jax.lax.fori_loop(0, L, nnz_step, acc0)

        def mxu_block():
            def blk_step(k, acc):
                bc = cols_ref[coff + k]
                a = st.panel_at(vals_ref, off // LANE + k * bm, bm, bk)
                xp = x_ref[pl.ds(pl.multiple_of(bc * bk, bk), bk), :]
                return acc + _mxu_dot(a, xp)
            return jax.lax.fori_loop(0, L, blk_step, acc0)

        return jax.lax.cond(tag_ref[d] == _VPU, vpu_block, mxu_block)

    accs = [member(w) for w in range(mw)]
    acc = accs[0] if mw == 1 else jnp.concatenate(accs, axis=0)
    carry[j] = acc
    y_ref[...] = acc.astype(y_ref.dtype)             # one store per trip


def _staged_kernel(tag_ref, off_ref, coff_ref, L_ref, cont_ref, cols_ref,
                   vals_ref, x_ref, carry_in_ref, y_ref, cring, vring,
                   mring, xgbuf, xpbuf, carry, csem, vsem, xgsem, xpsem, *,
                   bm: int, bk: int, dt: int, span: int, cspan: int,
                   mw: int):
    """Double-buffered twin of :func:`_kernel` (DESIGN.md §7.7).

    Trip ``g``'s windows start at the tile-aligned row at or below its
    first member's offsets (``staging.stage_trip_windows``); X operands
    are fetched one step ahead on two-deep rings.  Every DMA is started
    exactly once and waited exactly once."""
    g = pl.program_id(0)
    j = pl.program_id(1)
    st.stage_trip_windows(tag_ref, off_ref, coff_ref, cols_ref, vals_ref,
                          cring, vring, mring, csem, vsem, g=g, j=j,
                          ng=pl.num_programs(0), mw=mw,
                          vrows=span // LANE, crows=cspan // LANE)

    @pl.when(g == 0)
    def _carry_in():
        carry[j] = carry_in_ref[...]

    slot = g % 2
    vbase = st.window_base(off_ref[g * mw]) * LANE
    cbase = st.window_base(coff_ref[g * mw]) * LANE
    cont = cont_ref[g] > 0

    def member(w):
        d = g * mw + w
        loff, lcoff, L = off_ref[d] - vbase, coff_ref[d] - cbase, L_ref[d]
        acc0 = _start_acc(cont, carry, j, w, bm, dt)

        def vpu_block():
            # the gather moves to the DMA engine: trip nz+1's bm X rows
            # stream into the alternate buffer while trip nz's FMA runs
            def row_dma(ts, rr, nz):
                k = st.read(cring, slot, lcoff + rr * L + nz)
                return pltpu.make_async_copy(
                    x_ref.at[pl.ds(k, 1), pl.ds(j * dt, dt)],
                    xgbuf.at[ts, pl.ds(rr, 1)], xgsem.at[ts, rr])

            @pl.when(L > 0)
            def _():
                for rr in range(bm):
                    row_dma(0, rr, 0).start()

            def nnz_step(nz, acc):
                ts = nz % 2

                @pl.when(nz + 1 < L)
                def _():
                    for rr in range(bm):
                        row_dma(1 - ts, rr, nz + 1).start()

                for rr in range(bm):
                    row_dma(ts, rr, nz).wait()
                v = st.column([st.read(vring, slot, loff + rr * L + nz)
                               for rr in range(bm)])
                return acc + v * xgbuf[ts].astype(jnp.float32)
            return jax.lax.fori_loop(0, L, nnz_step, acc0)

        def mxu_block():
            # bcols-driven (bk, dt) X panel DMA, double-buffered
            def panel_dma(ts, k):
                bc = st.read(cring, slot, lcoff + k)
                return pltpu.make_async_copy(
                    x_ref.at[pl.ds(bc * bk, bk), pl.ds(j * dt, dt)],
                    xpbuf.at[ts], xpsem.at[ts])

            @pl.when(L > 0)
            def _():
                panel_dma(0, 0).start()

            def blk_step(k, acc):
                ts = k % 2

                @pl.when(k + 1 < L)
                def _():
                    panel_dma(1 - ts, k + 1).start()

                panel_dma(ts, k).wait()
                a = st.panel(mring, slot, loff // LANE + k * bm, bm, bk)
                return acc + _mxu_dot(a, xpbuf[ts])
            return jax.lax.fori_loop(0, L, blk_step, acc0)

        return jax.lax.cond(tag_ref[d] == _VPU, vpu_block, mxu_block)

    accs = [member(w) for w in range(mw)]
    acc = accs[0] if mw == 1 else jnp.concatenate(accs, axis=0)
    carry[j] = acc
    y_ref[...] = acc.astype(y_ref.dtype)             # one store per trip


def _prepare(blk_L, x, cont, bk: int, mw: int):
    """Defaults and paddings shared by both lowerings: no piece trips
    unless given, and X rows padded to whole block-columns (both
    branches are traced, the MXU one slices (bk, dt) panels)."""
    if cont is None:
        cont = jnp.zeros((blk_L.shape[0] // mw,), jnp.int32)
    pad = -x.shape[0] % max(bk, 8)
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, cont


@functools.partial(jax.jit, static_argnames=("bm", "bk", "mw", "interpret"))
def spmm_bcsr_fused(blk_tag: jax.Array, blk_off: jax.Array,
                    blk_coff: jax.Array, blk_L: jax.Array,
                    cols_flat: jax.Array, vals_flat: jax.Array,
                    x: jax.Array, cont=None, *, bm: int = 8, bk: int = 8,
                    mw: int = 1, interpret: bool = True) -> jax.Array:
    """Compute the WHOLE mixed plan: Y_ws (ws_rows, d_pad) = plan · X.

    blk_tag   : (B,) int32 — 0 = VPU ELL block, 1 = MXU block-row
    blk_off   : (B,) int32 — first slot of each block in vals_flat
    blk_coff  : (B,) int32 — first entry of each block in cols_flat
    blk_L     : (B,) int32 — trips: the block's longest row (VPU) or K
                (MXU)
    cols_flat : (Sc,) int32 — X row per slot (VPU) / block-column (MXU)
    vals_flat : (S,) float — slot values; MXU panels (K, bm, LANE)
    x         : (n, d_pad) float — columns padded to the lane tile
    cont      : (B // mw,) int32 — piece trips (default: none)
    mw        : CGCM merge width (DESIGN.md §7.9) — each grid step
                processes ``mw`` consecutive descriptors into one
                (mw*bm, dt) output trip; ``B`` must be a multiple.

    Returns workspace-ordered rows; the caller applies the plan's
    ``inv_perm`` gather to recover output row order.
    """
    from ..core.ccm import kernel_lane_tile  # lazy: core imports kernels

    num_blocks = blk_tag.shape[0]
    assert num_blocks % mw == 0, (num_blocks, mw)
    x, cont = _prepare(blk_L, x, cont, bk, mw)
    vals2 = st.stream_rows(vals_flat.astype(jnp.float32))
    n_pad, d_pad = x.shape
    dt = kernel_lane_tile(d_pad)
    nj = d_pad // dt

    return pl.pallas_call(
        functools.partial(_kernel, bm=bm, bk=bk, dt=dt, mw=mw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(num_blocks // mw, nj),
            in_specs=[
                pl.BlockSpec(vals2.shape, lambda g, j, *_: (0, 0)),
                pl.BlockSpec((n_pad, dt), lambda g, j, *_: (0, j)),
            ],
            out_specs=pl.BlockSpec((mw * bm, dt), lambda g, j, *_: (g, j)),
            scratch_shapes=[pltpu.VMEM((nj, mw * bm, dt), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_blocks * bm, d_pad),
                                       jnp.float32),
        interpret=interpret,
        name="bcsr_fused",
    )(blk_tag, blk_off, blk_coff, blk_L, cont, cols_flat,
      vals_flat.astype(jnp.float32), vals2, x)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bk", "mw", "span", "cspan", "interpret"))
def spmm_bcsr_fused_staged(blk_tag: jax.Array, blk_off: jax.Array,
                           blk_coff: jax.Array, blk_L: jax.Array,
                           cols_flat: jax.Array, vals_flat: jax.Array,
                           x: jax.Array, cont=None, *, span: int,
                           cspan: int, bm: int = 8, bk: int = 8,
                           mw: int = 1, interpret: bool = True
                           ) -> jax.Array:
    """The DMA-staged mixed dispatch (DESIGN.md §7.7) — same contract
    as :func:`spmm_bcsr_fused` and BIT-identical output.

    ``span``/``cspan`` are the workspace's ``max_span``/``max_cspan``
    windows (multiples of ``STAGE_TILE``).  Resident fast memory is two
    windows per stream plus two X operands, whatever nnz or ``n``."""
    from ..core.ccm import kernel_lane_tile  # lazy: core imports kernels

    assert blk_tag.shape[0] % mw == 0, (blk_tag.shape[0], mw)
    x, cont = _prepare(blk_L, x, cont, bk, mw)
    cols2 = st.stream_rows(cols_flat)
    vals2 = st.stream_rows(vals_flat.astype(jnp.float32))
    n_pad, d_pad = x.shape
    dt = kernel_lane_tile(d_pad)
    nj = d_pad // dt
    vrows, crows = span // LANE, cspan // LANE

    def call(tables, cont, carry_in):
        B = tables[0].shape[0]
        return pl.pallas_call(
            functools.partial(_staged_kernel, bm=bm, bk=bk, dt=dt,
                              span=span, cspan=cspan, mw=mw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(B // mw, nj),
                in_specs=[
                    pl.BlockSpec(memory_space=pl.ANY),    # cols (HBM)
                    pl.BlockSpec(memory_space=pl.ANY),    # vals (HBM)
                    pl.BlockSpec(memory_space=pl.ANY),    # X    (HBM)
                    pl.BlockSpec((mw * bm, dt), lambda g, j, *_: (0, j)),
                ],
                out_specs=pl.BlockSpec((mw * bm, dt),
                                       lambda g, j, *_: (g, j)),
                scratch_shapes=[
                    pltpu.SMEM((2, crows, LANE), jnp.int32),   # cols
                    pltpu.SMEM((2, vrows, LANE), jnp.float32),  # VPU vals
                    pltpu.VMEM((2, vrows, LANE), jnp.float32),  # MXU vals
                    pltpu.VMEM((2, bm, dt), jnp.float32),      # VPU X rows
                    pltpu.VMEM((2, bk, dt), jnp.float32),      # MXU X panel
                    pltpu.VMEM((nj, mw * bm, dt), jnp.float32),  # carry
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2, bm)),
                    pltpu.SemaphoreType.DMA((2,)),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B * bm, d_pad), jnp.float32),
            interpret=interpret,
            name="bcsr_fused_staged",
        )(*tables, cont, cols2, vals2, x, carry_in)

    return st.issue_in_calls(call, [blk_tag, blk_off, blk_coff, blk_L],
                             cont, mw * bm, d_pad, mw, bm)


def spmm_bcsr_fused_sharded(blk_tag: jax.Array, blk_off: jax.Array,
                            blk_coff: jax.Array, blk_L: jax.Array,
                            cols_flat: jax.Array, vals_flat: jax.Array,
                            x: jax.Array, cont=None, *, mesh, bm: int = 8,
                            bk: int = 8, mw: int = 1,
                            interpret: bool = True,
                            staging: str = "resident", span=0,
                            cspan=0, x_sharding: str = "replicated",
                            x_send=None, x_recv=None) -> jax.Array:
    """Run one mixed fused dispatch per chip under ``shard_map``.

    Descriptor tables are (C, ...) stacked per chip; ``x`` is either the
    replicated (n_pad, d_pad) operand or — under ``x_sharding="rows"`` —
    the stacked (C, P, bk, d_pad) owned-panel strips, assembled into
    each chip's compact local X workspace by the planner's exact-panel
    exchange before the kernel (DESIGN.md §7.8).  Returns (C, B*bm,
    d_pad) workspace rows sharded over the chip axis; the caller
    flattens and applies the sharded workspace's GLOBAL ``inv_perm``
    gather.

    ``staging="dma"`` lowers each chip through
    :func:`spmm_bcsr_fused_staged`; ``span``/``cspan`` may be per-chip
    tuples — chips are grouped by distinct window and each group gets
    rings sized for its own span (``staging.staged_dispatch``).
    """
    if cont is None:
        cont = jnp.zeros((blk_L.shape[0], blk_L.shape[1] // mw), jnp.int32)
    fn = _sharded_callable(mesh, bm, bk, interpret, staging,
                           st.chip_windows(span, mesh.size),
                           st.chip_windows(cspan, mesh.size), x_sharding,
                           mw)
    if x_sharding == "rows":
        return fn(blk_tag, blk_off, blk_coff, blk_L, cont, cols_flat,
                  vals_flat, x, x_send, x_recv)
    return fn(blk_tag, blk_off, blk_coff, blk_L, cont, cols_flat,
              vals_flat, x)


@functools.lru_cache(maxsize=32)
def _sharded_callable(mesh, bm: int, bk: int, interpret: bool,
                      staging: str = "resident", spans: tuple = (0,),
                      cspans: tuple = (0,),
                      x_sharding: str = "replicated", mw: int = 1):
    """jit-wrapped shard_map closure, memoized per (mesh, bm, bk,
    interpret, staging, spans, cspans, x_sharding, mw); evicted by
    ``core.jit_cache.clear_global_cache``."""
    from ..distributed.collectives import exact_panel_exchange

    (axis,) = mesh.axis_names

    if staging == "dma":
        def call(sp, cs):
            return functools.partial(spmm_bcsr_fused_staged, span=sp,
                                     cspan=cs, bm=bm, bk=bk, mw=mw,
                                     interpret=interpret)
        kernel = st.staged_dispatch(axis, spans, cspans, call)
    else:
        kernel = functools.partial(spmm_bcsr_fused, bm=bm, bk=bk, mw=mw,
                                   interpret=interpret)

    shard = P(axis)
    if x_sharding == "rows":
        def per_chip(tag, off, coff, L, cont, cols, vals, xo, xs, xr):
            xp = exact_panel_exchange(xo[0], xs[0], xr[0], axis)
            return kernel(tag[0], off[0], coff[0], L[0], cols[0],
                          vals[0], xp, cont[0])[None]
        return st.shard(per_chip, mesh, (shard,) * 10, shard)

    def per_chip(tag, off, coff, L, cont, cols, vals, xp):
        return kernel(tag[0], off[0], coff[0], L[0], cols[0], vals[0], xp,
                      cont[0])[None]
    return st.shard(per_chip, mesh, (shard,) * 7 + (P(),), shard)
