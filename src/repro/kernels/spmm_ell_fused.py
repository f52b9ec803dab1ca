"""Fused multi-segment CCM SpMM — the whole ELL plan in ONE dispatch.

The per-segment kernel (``spmm_csr.spmm_ell_segment``) pays one
``pallas_call`` plus one output scatter per ELL segment, so a
multi-bucket ``nnz_split`` plan multiplies launch overhead — exactly the
"redundant instructions" failure mode JITSPMM's one-artifact-per-
instance design (§IV-A, Table IV) eliminates.  Here the planner packs
every segment into a single flat slot array and emits a per-row-block
**descriptor table** (``blk_off``, ``blk_L``), and the whole plan runs
as one ``pallas_call`` over a static ``(row-blocks, d-tiles)`` grid.

A pure-ELL plan is the mixed plan with every block tagged VPU and the
column stream slot-parallel (``coff == off``), so these entry points run
the mixed kernel of :mod:`.spmm_bcsr_fused` with exactly that table —
one kernel body, one set of lowerings (resident, dma, sharded), and the
same VPU arithmetic the ELL kernel always had.
"""
from __future__ import annotations

import jax.numpy as jnp

from .spmm_bcsr_fused import (spmm_bcsr_fused, spmm_bcsr_fused_sharded,
                              spmm_bcsr_fused_staged)


def spmm_ell_fused(blk_off, blk_L, cols_flat, vals_flat, x, cont=None, *,
                   bm: int = 8, mw: int = 1, interpret: bool = True):
    """Y_ws (B*bm, d_pad) = plan · X for a pure-ELL descriptor table
    (see :func:`~.spmm_bcsr_fused.spmm_bcsr_fused`)."""
    return spmm_bcsr_fused(jnp.zeros_like(blk_off), blk_off, blk_off,
                           blk_L, cols_flat, vals_flat, x, cont, bm=bm,
                           mw=mw, interpret=interpret)


def spmm_ell_fused_staged(blk_off, blk_L, cols_flat, vals_flat, x,
                          cont=None, *, span: int, cspan: int, bm: int = 8,
                          mw: int = 1, interpret: bool = True):
    """The DMA-staged twin (DESIGN.md §7.7), bit-identical output."""
    return spmm_bcsr_fused_staged(
        jnp.zeros_like(blk_off), blk_off, blk_off, blk_L, cols_flat,
        vals_flat, x, cont, span=span, cspan=cspan, bm=bm, mw=mw,
        interpret=interpret)


def spmm_ell_fused_sharded(blk_off, blk_L, cols_flat, vals_flat, x,
                           cont=None, *, mesh, bm: int = 8, mw: int = 1,
                           interpret: bool = True,
                           staging: str = "resident", span=0, cspan=0,
                           x_sharding: str = "replicated", x_send=None,
                           x_recv=None):
    """One fused dispatch per chip under ``shard_map`` over (C, B)
    per-chip tables (see
    :func:`~.spmm_bcsr_fused.spmm_bcsr_fused_sharded`)."""
    return spmm_bcsr_fused_sharded(
        jnp.zeros_like(blk_off), blk_off, blk_off, blk_L, cols_flat,
        vals_flat, x, cont, mesh=mesh, bm=bm, mw=mw, interpret=interpret,
        staging=staging, span=span, cspan=cspan, x_sharding=x_sharding,
        x_send=x_send, x_recv=x_recv)
