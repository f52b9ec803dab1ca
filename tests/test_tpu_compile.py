"""Compile rehearsals of every fused lowering for a described TPU v5e.

Interpret mode cannot see what the chip's compiler refuses: loads at
offsets it cannot prove aligned, fast-memory overruns, scratch rings too
large for SMEM.  These tests hand real workspace shapes (d=128) to the
TPU compiler for a ``v5e:2x2`` topology that is described, not
attached — about a second or two each, no chip needed.  The topology is
described inside a fixture, so only the worker that runs this file
loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import (build_sharded_workspace, build_workspace,
                        random_csr)
from repro.core.plan import SPARSE_ATTN_MIXED_EINSUM, build_einsum_workspace
from repro.kernels.attn_fused import attn_fused, attn_fused_staged
from repro.kernels.spmm_bcsr_fused import (_sharded_callable,
                                           spmm_bcsr_fused,
                                           spmm_bcsr_fused_staged)
from repro.models.sparse_attention import sparse_attention_mask
from repro.platform import resident_fits

D = 128
i32, f32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep these compiles out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(a, sharding, dtype=None):
    a = np.asarray(a)
    dt = dtype or (i32 if a.dtype.kind in "iu" else f32)
    return jax.ShapeDtypeStruct(a.shape, dt, sharding=sharding)


def _tables(ws, sharding):
    """Shapes of a workspace's descriptor tables and streams, with the
    device constants' tile padding."""
    pad = lambda n: -(-n // 1024) * 1024  # noqa: E731
    return dict(
        tag=_spec(ws.blk_tag, sharding), off=_spec(ws.blk_off, sharding),
        coff=_spec(ws.blk_coff, sharding), L=_spec(ws.blk_L, sharding),
        cont=_spec(ws.blk_cont, sharding),
        cols=jax.ShapeDtypeStruct((pad(ws.cols_flat.shape[-1]),), i32,
                                  sharding=sharding),
        vals=jax.ShapeDtypeStruct((pad(ws.gather_flat.shape[-1]),), f32,
                                  sharding=sharding))


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.fixture(scope="module")
def powerlaw_2_18():
    n = 1 << 18
    a = random_csr(n, n, density=16 / n, family="powerlaw", seed=0)
    return a, build_workspace(a.row_ptr, a.col_indices, a.shape, D)


def test_mixed_dma_compiles_at_2_18_rows(one_chip, powerlaw_2_18):
    a, ws = powerlaw_2_18
    t = _tables(ws, one_chip)
    x = jax.ShapeDtypeStruct((a.n, D), f32, sharding=one_chip)
    compiled = _compile(
        lambda tag, off, coff, L, cols, vals, x, cont:
        spmm_bcsr_fused_staged(tag, off, coff, L, cols, vals, x, cont,
                               span=ws.max_span, cspan=ws.max_cspan,
                               interpret=False),
        t["tag"], t["off"], t["coff"], t["L"], t["cols"], t["vals"], x,
        t["cont"])
    assert "tpu_custom_call" in compiled.as_text()


def test_ell_dma_compiles_at_2_18_rows(one_chip, powerlaw_2_18):
    """The all-VPU plan (mxu_gain=0), the layout of a power-law graph."""
    a, _ = powerlaw_2_18
    ws = build_workspace(a.row_ptr, a.col_indices, a.shape, D,
                         mxu_gain=0.0)
    t = _tables(ws, one_chip)
    x = jax.ShapeDtypeStruct((a.n, D), f32, sharding=one_chip)
    _compile(
        lambda tag, off, coff, L, cols, vals, x, cont:
        spmm_bcsr_fused_staged(tag, off, coff, L, cols, vals, x, cont,
                               span=ws.max_span, cspan=ws.max_cspan,
                               interpret=False),
        t["tag"], t["off"], t["coff"], t["L"], t["cols"], t["vals"], x,
        t["cont"])


def _attention_operands(ws, S, sharding):
    q = jax.ShapeDtypeStruct((ws.ws_rows, D), f32, sharding=sharding)
    kv = jax.ShapeDtypeStruct((S, D), f32, sharding=sharding)
    return q, kv


def test_attention_dma_compiles_at_4096(one_chip):
    """longformer-1.4b's window+global mask, head_dim 128."""
    S = 4096
    m = sparse_attention_mask(S, 512, 64)
    ws = build_einsum_workspace(SPARSE_ATTN_MIXED_EINSUM, m.row_ptr,
                                m.col_indices, m.shape, D)
    t = _tables(ws, one_chip)
    q, kv = _attention_operands(ws, S, one_chip)
    _compile(lambda tag, off, coff, L, cols, vals, q, k, v, cont:
             attn_fused_staged(tag, off, coff, L, cols, vals, q, k, v,
                               cont, span=ws.max_span, cspan=ws.max_cspan,
                               interpret=False),
             t["tag"], t["off"], t["coff"], t["L"], t["cols"], t["vals"],
             q, kv, kv, t["cont"])


@pytest.mark.parametrize("b", (8, 16, 32, 64, 128))
def test_attention_panel_edges_compile_at_4096(one_chip, b):
    """Mellum2's causal window of 1,024, head_dim 128, at each square
    panel edge: an all-MXU plan, lowered without the VPU branch."""
    S = 4096
    m = sparse_attention_mask(S, 1024, 0)
    ws = build_einsum_workspace(SPARSE_ATTN_MIXED_EINSUM, m.row_ptr,
                                m.col_indices, m.shape, D, row_block=b,
                                bk=b)
    assert not np.any(ws.blk_tag == 0)
    t = _tables(ws, one_chip)
    q, kv = _attention_operands(ws, S, one_chip)
    compiled = _compile(
        lambda tag, off, coff, L, cols, vals, q, k, v, cont:
        attn_fused_staged(tag, off, coff, L, cols, vals, q, k, v, cont,
                          span=ws.max_span, cspan=ws.max_cspan, bm=b,
                          bk=b, vpu=False, interpret=False),
        t["tag"], t["off"], t["coff"], t["L"], t["cols"], t["vals"],
        q, kv, kv, t["cont"])
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("family", ("spmm", "attention"))
def test_resident_compiles_at_small_size(one_chip, family):
    a = random_csr(256, 256, density=0.05, family="banded", seed=1)
    ws = build_workspace(a.row_ptr, a.col_indices, a.shape, D)
    assert ws.has_mxu
    assert resident_fits(ws.num_blocks, ws.gather_flat.size,
                         ws.cols_flat.size, 4 * a.n * 2 * D)
    t = _tables(ws, one_chip)
    args = [t["tag"], t["off"], t["coff"], t["L"], t["cols"], t["vals"]]
    if family == "spmm":
        x = jax.ShapeDtypeStruct((a.n, D), f32, sharding=one_chip)
        _compile(lambda *a: spmm_bcsr_fused(*a, interpret=False),
                 *args, x, t["cont"])
    else:
        q, kv = _attention_operands(ws, a.n, one_chip)
        _compile(lambda *a: attn_fused(*a, interpret=False),
                 *args, q, kv, kv, t["cont"])


def test_sharded_mixed_dma_compiles_on_four_chips(topo):
    """The row-sharded X path: exact-panel all_to_all, then one staged
    kernel per chip."""
    n = 1 << 16
    a = random_csr(n, n, density=16 / n, family="powerlaw", seed=2)
    sw = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape, D,
                                 n_chips=4, x_sharding="rows")
    mesh = Mesh(np.asarray(topo.devices[:4]), ("chips",))
    on_chips = NamedSharding(mesh, P("chips"))
    t = _tables(sw, on_chips)
    for k in ("cols", "vals"):
        t[k] = jax.ShapeDtypeStruct((4,) + t[k].shape, t[k].dtype,
                                    sharding=on_chips)
    strips = jax.ShapeDtypeStruct((4, sw.x_own_panels, sw.bk, D), f32,
                                  sharding=on_chips)
    fn = _sharded_callable(mesh, sw.row_block, sw.bk, False, "dma",
                           tuple(int(s) for s in sw.chip_span),
                           tuple(int(s) for s in sw.chip_cspan), "rows",
                           sw.merge_width)
    compiled = fn.lower(t["tag"], t["off"], t["coff"], t["L"], t["cont"],
                        t["cols"], t["vals"], strips,
                        _spec(sw.x_send, on_chips),
                        _spec(sw.x_recv, on_chips)).compile()
    hlo = compiled.as_text()
    assert "all-to-all" in hlo and "tpu_custom_call" in hlo
