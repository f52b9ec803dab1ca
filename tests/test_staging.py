"""Acceptance suite for double-buffered slot-panel DMA staging
(DESIGN.md §7.7, staging="dma" on the fused backends).

What staging must preserve — and what this module pins:

  * BIT-identity: the staged lowering reorders nothing, it only moves
    operands from resident VMEM buffers to per-block DMA panels, so
    staged == resident exactly (all-VPU and mixed plans, all three
    strategies, single-chip and sharded).
  * the Table IV invariant: still exactly ONE pallas_call per chip per
    forward, asserted via DISPATCH_COUNTS and on the traced jaxpr.
  * specialization identity: the resolved staging mode is part of the
    jit-cache key ("resident" and "dma" artifacts never alias), and
    "auto" resolves per platform (interpret -> resident, TPU -> dma).
  * workspace metadata: every descriptor's fixed DMA window
    [off, off + max_span) / [coff, coff + max_cspan) stays in bounds.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CSRMatrix, MXU_TAG, build_mixed_plan,
                        build_fused_workspace, build_sharded_workspace,
                        choose_merge_width, compile_spmm, random_csr, spmm)
from repro.core.jit_cache import JitCache
from repro.core.plan import LANE, STRATEGIES, STAGE_TILE
from repro.kernels import ops
from repro.kernels.ops import resolve_staging

ROOT = Path(__file__).resolve().parents[1]
N_DEV = len(jax.devices())
MAX_CHIPS = min(N_DEV, 4)

# the fused backend with every block-row tagged VPU, and as the
# structure tags it; parametrized by key
FUSED = {"vpu": dict(backend="pallas_bcsr", mxu_gain=0.0),
         "pallas_bcsr": dict(backend="pallas_bcsr")}


def _mixed_csr(seed=0, m=48, n=64):
    """Dense block-rows (MXU bait) + ragged sparse tail (VPU bait) —
    staging must survive both panel shapes in one dispatch."""
    rng = np.random.default_rng(seed)
    dense = np.zeros((m, n), np.float32)
    for i in range(16):
        j0 = (i // 8) * 16
        dense[i, j0:j0 + 16] = rng.standard_normal(16)
    for i in range(16, m):
        k = rng.integers(1, 4)
        dense[i, rng.choice(n, size=k, replace=False)] = (
            rng.standard_normal(k))
    return CSRMatrix.from_dense(dense)


def _x(n, d, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((n, d)), jnp.float32)


# -- bit-identity ----------------------------------------------------------

@pytest.mark.parametrize("backend", FUSED)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_staged_bit_identical_to_resident(backend, strategy):
    a = _mixed_csr(seed=2)
    x = _x(a.n, 20, seed=3)
    y_res = spmm(a, x, strategy=strategy, **FUSED[backend],
                 interpret=True, staging="resident", cache=JitCache())
    y_dma = spmm(a, x, strategy=strategy, **FUSED[backend],
                 interpret=True, staging="dma", cache=JitCache())
    assert np.array_equal(np.asarray(y_dma), np.asarray(y_res))


@pytest.mark.parametrize("backend", FUSED)
def test_staged_bit_identical_on_skewed_powerlaw(backend):
    a = random_csr(120, 96, density=0.06, family="powerlaw", seed=4)
    x = _x(a.n, 24, seed=5)
    y_res = spmm(a, x, **FUSED[backend], interpret=True,
                 staging="resident", cache=JitCache())
    y_dma = spmm(a, x, **FUSED[backend], interpret=True,
                 staging="dma", cache=JitCache())
    assert np.array_equal(np.asarray(y_dma), np.asarray(y_res))


def _run_workspace(ws, a, x, staging):
    """Dispatch a hand-packed workspace through the op layer."""
    vals = jnp.concatenate([jnp.asarray(a.vals, jnp.float32),
                            jnp.zeros((1,), jnp.float32)])
    x_pad = jnp.pad(x, ((0, 0), (0, -x.shape[1] % 128)))
    y = ops.spmm_bcsr_fused_op(
        jnp.asarray(ws.blk_tag), jnp.asarray(ws.blk_off),
        jnp.asarray(ws.blk_coff), jnp.asarray(ws.blk_L),
        jnp.asarray(ws.cols_flat), vals[jnp.asarray(ws.gather_flat)], x_pad,
        jnp.asarray(ws.blk_cont), bk=ws.bk, mw=ws.merge_width,
        interpret=True, staging=staging, span=ws.max_span,
        cspan=ws.max_cspan)
    return np.asarray(y[jnp.asarray(ws.inv_perm), :x.shape[1]])


@pytest.mark.parametrize("mixed", (False, True))
@pytest.mark.parametrize("threshold", (0, 16))
def test_split_blocks_bit_identical_to_unsplit(mixed, threshold,
                                              pack_with_window):
    """A block wider than the platform's staging window is packed as
    piece trips that carry the accumulator.  At a tiny window every
    long block splits, and both lowerings still reproduce the unsplit
    result bit for bit."""
    a = _mixed_csr(seed=10)
    x = _x(a.n, 20, seed=11)
    plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 20,
                            mxu_gain=4.0 if mixed else 0.0)
    mw = choose_merge_width(a.row_ptr, merge_threshold=threshold)
    ws0 = build_fused_workspace(plan, merge_width=mw)
    ws = pack_with_window(plan, 32, merge_width=mw)
    assert ws0.blk_cont.sum() == 0 < ws.blk_cont.sum()
    assert ws.max_span < ws0.max_span or ws0.max_span <= 2 * STAGE_TILE
    y0 = _run_workspace(ws0, a, x, "resident")
    for staging in ("resident", "dma"):
        assert np.array_equal(_run_workspace(ws, a, x, staging), y0)


@pytest.mark.parametrize("path", ("solo", "sharded", "batched", "grad"))
def test_split_blocks_through_the_entry_points(path, monkeypatch):
    """Piece trips reach every dispatch the entry points build — solo,
    chip-stacked, request-stacked, and the transposed artifact of the
    gradient — and each still matches its unsplit twin bit for bit."""
    from repro import platform
    from repro.core import compile_batched_spmm
    from repro.core import plan as plan_mod
    a = _mixed_csr(seed=14)
    x = _x(a.n, 16, seed=15)
    vals = jnp.asarray(a.vals)

    def run():
        """(output, piece trips in the dispatched tables)"""
        if path == "batched":
            c = compile_batched_spmm([a, a], 16, backend="pallas_bcsr",
                                     interpret=True, staging="dma",
                                     cache=JitCache())
            y = c([vals, vals], [x, x])[1]
            return np.asarray(y), int(c._consts.cont.sum())
        c = compile_spmm(a, 16, backend="pallas_bcsr", interpret=True,
                         staging="dma", cache=JitCache(),
                         n_chips=MAX_CHIPS if path == "sharded" else None)
        if path == "grad":
            y = jax.grad(lambda v, xx: jnp.sum(c(v, xx) ** 2),
                         argnums=1)(vals, x)
            return np.asarray(y), int(c._transpose._fused.cont.sum())
        consts = c._sharded if path == "sharded" else c._fused
        return np.asarray(c(vals, x)), int(consts.cont.sum())

    y0, pieces0 = run()
    monkeypatch.setattr(plan_mod, "stage_limits", lambda: platform.StageLimits(
        window=32, descs=platform.stage_limits().descs))
    y, pieces = run()
    assert pieces0 == 0 < pieces
    assert np.array_equal(y, y0)


def test_long_stream_issued_as_calls_bit_identical(monkeypatch,
                                                  pack_with_window):
    """A stream with more descriptors than one call's SMEM tables hold
    runs as a sequence of calls, a split block continuing across a call
    boundary; the output equals the single call's bit for bit and every
    call counts as a launch."""
    from repro import platform
    from repro.kernels import staging as staging_mod
    a = _mixed_csr(seed=12)
    x = _x(a.n, 16, seed=13)
    plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16)
    ws = pack_with_window(plan, 32)
    per = int(np.flatnonzero(ws.blk_cont)[0])   # a piece opens call 2
    assert per > 0 and ws.num_blocks > per
    y1 = _run_workspace(ws, a, x, "dma")
    monkeypatch.setattr(staging_mod, "stage_limits",
                        lambda: platform.StageLimits(window=32, descs=per))
    jax.clear_caches()
    ops.reset_dispatch_counts()
    y = _run_workspace(ws, a, x, "dma")
    assert ops.DISPATCH_COUNTS["bcsr_fused_dma"] == -(-ws.num_blocks // per)
    assert np.array_equal(y, y1)


@pytest.mark.parametrize("backend", FUSED)
def test_staged_sharded_bit_identical(backend):
    """sharded+staged == sharded+resident == unsharded+staged: staging
    and sharding must compose without touching a single bit."""
    a = _mixed_csr(seed=6, m=56)
    x = _x(a.n, 16, seed=7)
    y0 = spmm(a, x, **FUSED[backend], interpret=True, staging="dma",
              cache=JitCache())
    for chips in range(1, MAX_CHIPS + 1):
        y_res = spmm(a, x, **FUSED[backend], interpret=True,
                     staging="resident", n_chips=chips, cache=JitCache())
        y_dma = spmm(a, x, **FUSED[backend], interpret=True,
                     staging="dma", n_chips=chips, cache=JitCache())
        assert np.array_equal(np.asarray(y_dma), np.asarray(y_res)), chips
        assert np.array_equal(np.asarray(y_dma), np.asarray(y0)), chips


def test_staged_gradients_bit_match_resident():
    """The custom VJP routes the backward through a transposed artifact
    that must inherit the staging mode (and stay bit-identical)."""
    a = _mixed_csr(seed=8)
    x = _x(a.n, 12, seed=9)
    vals = jnp.asarray(a.vals)
    for backend in FUSED:
        c_res = compile_spmm(a, 12, **FUSED[backend], interpret=True,
                             staging="resident", cache=JitCache())
        c_dma = compile_spmm(a, 12, **FUSED[backend], interpret=True,
                             staging="dma", cache=JitCache())

        def loss(c):
            return lambda v, xx: jnp.sum(jnp.tanh(c(v, xx)))

        gr = jax.grad(loss(c_res), argnums=(0, 1))(vals, x)
        gd = jax.grad(loss(c_dma), argnums=(0, 1))(vals, x)
        assert np.array_equal(np.asarray(gr[0]), np.asarray(gd[0]))
        assert np.array_equal(np.asarray(gr[1]), np.asarray(gd[1]))
        assert c_dma._transpose is not None
        assert c_dma._transpose.staging == "dma"


# -- one pallas_call per chip ---------------------------------------------

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = v if hasattr(v, "eqns") else getattr(v, "jaxpr", None)
            if hasattr(inner, "eqns"):
                yield from _iter_eqns(inner)


@pytest.mark.parametrize("backend,counter",
                         [("vpu", "bcsr_fused"),
                          ("pallas_bcsr", "bcsr_fused")])
def test_staged_trace_is_one_pallas_call(backend, counter):
    a = _mixed_csr(seed=10)
    x = _x(a.n, 16, seed=11)
    c = compile_spmm(a, 16, **FUSED[backend], interpret=True,
                     staging="dma", cache=JitCache())
    jaxpr = jax.make_jaxpr(lambda v, xx: c(v, xx))(jnp.asarray(a.vals), x)
    pallas = [e for e in _iter_eqns(jaxpr.jaxpr)
              if e.primitive.name == "pallas_call"]
    assert len(pallas) == 1

    ops.reset_dispatch_counts()
    y = c(jnp.asarray(a.vals), x)
    jax.block_until_ready(y)
    assert ops.DISPATCH_COUNTS[counter] == 1
    assert ops.DISPATCH_COUNTS[counter + "_dma"] == 1


@pytest.mark.parametrize("backend,counter",
                         [("vpu", "bcsr_fused"),
                          ("pallas_bcsr", "bcsr_fused")])
def test_staged_sharded_trace_is_one_pallas_call_per_chip(backend,
                                                          counter):
    a = _mixed_csr(seed=12, m=56)
    x = _x(a.n, 16, seed=13)
    c = compile_spmm(a, 16, **FUSED[backend], interpret=True,
                     staging="dma", n_chips=MAX_CHIPS, cache=JitCache())
    jaxpr = jax.make_jaxpr(lambda v, xx: c(v, xx))(jnp.asarray(a.vals), x)
    eqns = list(_iter_eqns(jaxpr.jaxpr))
    shard_eqns = [e for e in eqns if e.primitive.name == "shard_map"]
    assert len(shard_eqns) == 1
    body = shard_eqns[0].params["jaxpr"]
    body = body if hasattr(body, "eqns") else body.jaxpr
    in_body = [e for e in _iter_eqns(body)
               if e.primitive.name == "pallas_call"]
    assert len(in_body) == 1

    ops.reset_dispatch_counts()
    y = c(jnp.asarray(a.vals), x)
    jax.block_until_ready(y)
    assert ops.DISPATCH_COUNTS[counter] == MAX_CHIPS
    assert ops.DISPATCH_COUNTS[counter + "_dma"] == MAX_CHIPS


def test_resident_forward_counts_no_dma_dispatch():
    a = _mixed_csr(seed=14)
    x = _x(a.n, 8, seed=15)
    c = compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                     staging="resident", cache=JitCache())
    ops.reset_dispatch_counts()
    jax.block_until_ready(c(jnp.asarray(a.vals), x))
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == 1
    assert ops.DISPATCH_COUNTS["bcsr_fused_dma"] == 0


# -- specialization identity ----------------------------------------------

def test_jit_cache_keys_on_staging_mode():
    a = _mixed_csr(seed=16)
    cache = JitCache()
    c_res = compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                         staging="resident", cache=cache)
    c_dma = compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                         staging="dma", cache=cache)
    assert c_res is not c_dma
    assert cache.stats()["entries"] == 2
    # repeat hits, and "auto" under interpret mode resolves to resident
    assert compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                        staging="dma", cache=cache) is c_dma
    assert compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                        staging="auto", cache=cache) is c_res
    assert compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                        cache=cache) is c_res


def test_resolve_staging_contract():
    assert resolve_staging(None, True) == "resident"
    assert resolve_staging("auto", True) == "resident"
    assert resolve_staging(None, False) == "dma"
    assert resolve_staging("dma", True) == "dma"
    assert resolve_staging("resident", False) == "resident"
    with pytest.raises(ValueError):
        resolve_staging("mmap", True)
    # the knob only exists on the fused dispatch
    a = _mixed_csr(seed=17)
    with pytest.raises(ValueError):
        compile_spmm(a, 8, backend="ref", staging="dma", cache=JitCache())


def test_op_wrappers_refuse_dma_without_windows():
    """Direct kernel-layer callers that never built a workspace must not
    be auto-routed onto the staged path with zero-size scratch: auto
    falls back to resident, an explicit "dma" without windows raises."""
    a = _mixed_csr(seed=20)
    x = _x(a.n, 8, seed=21)
    c = compile_spmm(a, 8, backend="pallas_bcsr", mxu_gain=0.0,
                     interpret=True, staging="resident", cache=JitCache())
    fw = c._fused
    vals_flat = jnp.concatenate(
        [jnp.asarray(a.vals, jnp.float32), jnp.zeros((1,))])[fw.gather_flat]
    x_pad = jnp.pad(x, ((0, 0), (0, 128 - x.shape[1])))
    with pytest.raises(ValueError):
        ops.spmm_bcsr_fused_op(fw.blk_tag, fw.blk_off, fw.blk_coff,
                               fw.blk_L, fw.cols_flat, vals_flat, x_pad,
                               interpret=True,
                               staging="dma")      # no span/cspan
    # auto (None) without windows stays resident even if it would
    # otherwise resolve to dma — and produces the right answer
    ops.reset_dispatch_counts()
    y = ops.spmm_bcsr_fused_op(fw.blk_tag, fw.blk_off, fw.blk_coff,
                               fw.blk_L, fw.cols_flat, vals_flat, x_pad,
                               interpret=True)
    assert ops.DISPATCH_COUNTS["bcsr_fused_dma"] == 0
    y_ref = spmm(a, x, backend="ref", cache=JitCache())
    np.testing.assert_allclose(np.asarray(y[fw.inv_perm, :8]),
                               np.asarray(y_ref), rtol=1e-4, atol=1e-4)


# -- workspace DMA-window metadata ----------------------------------------

def test_workspace_staging_metadata_invariants():
    a = _mixed_csr(seed=18, m=50)
    plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16)
    ws = build_fused_workspace(plan)
    assert np.any(ws.blk_tag == MXU_TAG)
    bm = ws.row_block
    L = ws.blk_L.astype(np.int64)
    mxu = ws.blk_tag == MXU_TAG
    # MXU value panels are lane-padded to (bm, LANE) aligned tiles
    np.testing.assert_array_equal(
        ws.blk_span, np.where(mxu, L * bm * LANE, bm * L))
    np.testing.assert_array_equal(
        ws.blk_cspan, np.where(mxu, L, bm * L))
    assert np.all(ws.blk_off[mxu] % STAGE_TILE == 0)
    assert ws.max_span % STAGE_TILE == 0
    assert ws.max_cspan % STAGE_TILE == 0
    assert ws.max_span >= int(ws.blk_span.max(initial=0))
    # the fixed window never reads past either stream
    assert np.all(ws.blk_off + ws.max_span <= ws.gather_flat.shape[0])
    assert np.all(ws.blk_coff + ws.max_cspan <= ws.cols_flat.shape[0])


def test_sharded_workspace_windows_cover_every_chip():
    a = _mixed_csr(seed=19, m=50)
    for gain in (0.0, 4.0):      # all-VPU, and as the structure tags it
        sw = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape,
                                     16, n_chips=3, mxu_gain=gain)
        # windows are PER CHIP since the hot-shard fix: each chip's
        # window must cover ITS OWN largest block (pad blocks span 0),
        # and max_span stays the cross-chip max for introspection
        L = sw.blk_L.astype(np.int64)
        spans = np.where(sw.blk_tag == MXU_TAG,
                         L * sw.row_block * LANE, sw.row_block * L)
        cspans = np.where(sw.blk_tag == MXU_TAG, L, sw.row_block * L)
        chip_span = np.asarray(sw.chip_span)
        chip_cspan = np.asarray(sw.chip_cspan)
        assert np.all(chip_span >= spans.max(axis=1, initial=0))
        assert np.all(chip_cspan >= cspans.max(axis=1, initial=0))
        assert sw.max_span == int(chip_span.max(initial=0))
        assert sw.max_cspan == int(chip_cspan.max(initial=0))
        assert np.all(sw.blk_off + chip_span[:, None]
                      <= sw.gather_flat.shape[1])
        assert np.all(sw.blk_coff + chip_cspan[:, None]
                      <= sw.cols_flat.shape[1])


# -- 8-device acceptance ---------------------------------------------------

def test_acceptance_staged_on_8_device_mesh():
    """ISSUE acceptance: staged == resident BIT-identical on an 8-chip
    host mesh for the all-VPU and the mixed plan, with exactly n_chips
    staged dispatches per forward."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    code = textwrap.dedent("""
        import jax, numpy as np, jax.numpy as jnp
        assert len(jax.devices()) == 8
        from repro.core import random_csr, spmm
        from repro.core.jit_cache import JitCache
        from repro.kernels import ops
        a = random_csr(128, 96, density=0.06, family="powerlaw", seed=21)
        x = jnp.asarray(np.random.default_rng(22)
                        .standard_normal((96, 16)), jnp.float32)
        for gain in (0.0, 4.0):  # all-VPU, and as the structure tags it
            kw = dict(backend="pallas_bcsr", mxu_gain=gain, interpret=True,
                      n_chips=8)
            y_res = spmm(a, x, staging="resident", cache=JitCache(), **kw)
            ops.reset_dispatch_counts()
            y_dma = spmm(a, x, staging="dma", cache=JitCache(), **kw)
            assert ops.DISPATCH_COUNTS["bcsr_fused"] == 8, gain
            assert ops.DISPATCH_COUNTS["bcsr_fused_dma"] == 8, gain
            assert np.array_equal(np.asarray(y_dma),
                                  np.asarray(y_res)), gain
        print("STAGED-8DEV-OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "STAGED-8DEV-OK" in out.stdout
