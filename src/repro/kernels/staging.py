"""Scaffolding the fused kernels share: the (rows, LANE) stream view,
the double-buffered window DMAs, per-chip window dispatch and the
sequence of calls a long descriptor stream is issued as.

Why the streams are 2-D (DESIGN.md §7.7): Mosaic addresses a 1-D HBM
or VMEM buffer only at offsets it can prove are multiples of its
1024-element tiling, and loads one vector tile at a time.  Viewed as
``(S // LANE, LANE)``, a window of rows can start at any row, values
read as scalars come from an SMEM ring, and an MXU panel is one aligned
``(bm, LANE)`` tile of a VMEM ring.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..platform import LANE, STAGE_TILE, stage_limits

TILE_ROWS = STAGE_TILE // LANE


def stream_rows(flat: jax.Array) -> jax.Array:
    """``(S,)`` -> ``(ceil(S / STAGE_TILE) * TILE_ROWS, LANE)``; the
    padding (a copy) is skipped when the dispatch layer already padded
    the stream, which it does for every planned workspace."""
    pad = -flat.shape[0] % STAGE_TILE
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANE)


def read(ring, slot, s):
    """Scalar ``s`` of ring buffer ``slot`` (an SMEM ``(2, rows, LANE)``
    ring holding a window of a flat stream)."""
    return ring[slot, s // LANE, s % LANE]


def window_base(off):
    """First row of the window staged for a trip starting at slot
    ``off``: rounded down to a whole tile, so MXU panels (tile-aligned
    by the packer) land on aligned rows of the ring."""
    return pl.multiple_of((off // STAGE_TILE) * TILE_ROWS, TILE_ROWS)


def window_copy(stream, ring, sems, slot, base, rows):
    return pltpu.make_async_copy(stream.at[pl.ds(base, rows), :],
                                 ring.at[slot], sems.at[slot])


def stage_trip_windows(tag_ref, off_ref, coff_ref, cols_ref, vals_ref,
                       cring, vring, mring, csem, vsem, *, g, j, ng,
                       mw: int, vrows: int, crows: int, mxu_tag: int = 1,
                       vpu: bool = True):
    """The trip-window pipeline of the staged kernels: at the first
    d-tile of trip ``g``, start trip ``g + 1``'s windows into the other
    ring slot and wait for trip ``g``'s.  A trip's members share one tag
    (the packer guarantees it), so its value window goes to the SMEM
    ring (VPU scalars) or the VMEM ring (MXU panels), never both.
    ``vpu`` False (a stream of MXU trips only) leaves the SMEM ring
    out.  Every copy is started exactly once and waited exactly once."""
    def trip_dmas(slot, grp, op):
        first = grp * mw
        vbase = window_base(off_ref[first])
        getattr(window_copy(cols_ref, cring, csem, slot,
                            window_base(coff_ref[first]), crows), op)()
        if not vpu:
            getattr(window_copy(vals_ref, mring, vsem, slot, vbase,
                                vrows), op)()
            return
        mxu = tag_ref[first] == mxu_tag

        @pl.when(mxu)
        def _():
            getattr(window_copy(vals_ref, mring, vsem, slot, vbase,
                                vrows), op)()

        @pl.when(jnp.logical_not(mxu))
        def _():
            getattr(window_copy(vals_ref, vring, vsem, slot, vbase,
                                vrows), op)()

    @pl.when((g == 0) & (j == 0))
    def _warmup():
        trip_dmas(0, 0, "start")

    @pl.when((j == 0) & (g + 1 < ng))
    def _prefetch_next():
        trip_dmas((g + 1) % 2, g + 1, "start")

    @pl.when(j == 0)
    def _arrive():
        trip_dmas(g % 2, g, "wait")


def panel_at(ref, row, bm: int, bk: int):
    """One MXU value panel: rows ``[row, row + bm)`` of a ``(rows,
    LANE)`` VMEM view, first ``bk`` lanes.  ``row`` is tile-aligned by
    the packer when ``bm`` is a multiple of 8."""
    tile = ref[pl.ds(pl.multiple_of(row, 8 if bm % 8 == 0 else 1), bm), :]
    return tile if bk == LANE else tile[:, :bk]


def panel(ring, slot, row, bm: int, bk: int):
    return panel_at(ring.at[slot], row, bm, bk)


def column(vs):
    """A ``(len(vs), 1)`` f32 column from scalars."""
    return jnp.concatenate([jnp.full((1, 1), v, jnp.float32) for v in vs],
                           axis=0)


def call_descs(mw: int) -> int:
    """Descriptors one staged call holds in SMEM (a multiple of the
    merge width)."""
    d = stage_limits().descs
    return d - d % mw


def num_calls(num_blocks: int, mw: int) -> int:
    return max(-(-num_blocks // call_descs(mw)), 1)


def issue_in_calls(call, tables, cont, carry_rows: int, d_pad: int,
                   mw: int, bm: int):
    """Run ``call(tables, cont, carry_in) -> y`` over the descriptor
    stream in calls of at most :func:`call_descs` descriptors.

    Each call's first trip may continue a block split by the previous
    call: ``carry_in`` is the previous call's last output block, which
    holds the running accumulator of that block (every trip writes its
    accumulator).  One stream that fits is one call; a longer one pads
    to whole calls with inert descriptors and scans, so the kernel is
    traced and compiled once."""
    B = tables[0].shape[0]
    per = call_descs(mw)
    zero = jnp.zeros((carry_rows, d_pad), jnp.float32)
    if B <= per:
        return call(tables, cont, zero)
    n = -(-B // per)
    pad = n * per - B
    tables = [jnp.pad(t, (0, pad)).reshape(n, per) for t in tables]
    cont = jnp.pad(cont, (0, pad // mw)).reshape(n, per // mw)

    def step(carry, xs):
        y = call(list(xs[:-1]), xs[-1], carry)
        return y[-carry_rows:], y

    _, ys = jax.lax.scan(step, zero, (*tables, cont))
    return ys.reshape(n * per * bm, d_pad)[:B * bm]


def chip_windows(v, n_chips: int) -> tuple:
    """Normalize a DMA window argument to a per-chip tuple: ints
    broadcast; sequences (``ShardedFusedWorkspace.chip_span``) pass
    through."""
    if hasattr(v, "__len__"):
        if len(v) != n_chips:
            raise ValueError(
                f"per-chip DMA windows need one entry per chip: got "
                f"{len(v)} for {n_chips} chips")
        return tuple(int(s) for s in v)
    return (int(v),) * n_chips


def staged_dispatch(axis: str, spans: tuple, cspans: tuple, call):
    """Per-chip staged-kernel specialization (the hot-shard window fix).

    Chips are grouped by distinct (span, cspan) window and each group
    gets its own staged kernel with rings sized for THAT window;
    ``lax.switch`` on the chip axis index picks the group, so a cold
    chip's rings do not scale with the hottest shard's span.  With a
    uniform window the switch collapses to a direct call.

    ``call(span, cspan)`` returns the kernel callable for one window.
    """
    groups = sorted(set(zip(spans, cspans)))
    if len(groups) == 1:
        return call(*groups[0])
    idx = [groups.index(w) for w in zip(spans, cspans)]

    def dispatch(*operands):
        branch = jnp.asarray(idx, jnp.int32)[jax.lax.axis_index(axis)]
        return jax.lax.switch(branch, [call(*g) for g in groups],
                              *operands)
    return dispatch


def shard(per_chip, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(per_chip, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))
