"""Cross-chip X sharding (x_sharding="rows", DESIGN.md §7.8).

What the X-sharded dispatch must preserve — and what this module pins:

  * BIT-identity with the replicated sharded path (and hence with the
    unsharded fused path): the exact-panel exchange copies values, the
    remapped column stream addresses the same rows, the accumulation
    order never changes — all three strategies x all-VPU and mixed
    plans x both staging modes x 1..N chips, forward AND gradient (the
    transposed artifact inherits the knob).
  * the Table IV invariant: still exactly one pallas_call per chip per
    forward (plus one all_to_all collective), asserted on DISPATCH
    counters and the traced jaxpr.
  * specialization identity: the resolved x_sharding joins the
    jit-cache key ("replicated" and "rows" artifacts never alias), and
    "auto" resolves per mesh/interpret like staging.
  * plan-time fetch tables: every chip fetches exactly its touched
    panel set, owners/ranks are consistent, and the remapped column
    stream stays inside the compact local X workspace.
  * the hot-shard window fix riding along: per-chip staged DMA windows
    (chip_span/chip_cspan) no longer all scale with the hottest shard.
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

from repro.core import (CSRMatrix, build_sharded_workspace, compile_spmm,
                        random_csr, spmm)
from repro.core.jit_cache import JitCache
from repro.core.plan import MXU_TAG, STRATEGIES
from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[1]
N_DEV = len(jax.devices())
MAX_CHIPS = min(N_DEV, 4)

# the fused backend with every block-row tagged VPU, and as the
# structure tags it; parametrized by key
FUSED = {"vpu": dict(backend="pallas_bcsr", mxu_gain=0.0),
         "pallas_bcsr": dict(backend="pallas_bcsr")}


def _mixed_csr(seed=0, m=48, n=64):
    """Dense block-rows (MXU bait) + ragged sparse tail (VPU bait), so
    the fetch tables carry both VPU row panels and MXU block-columns."""
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


def _hot_csr(m=64, n=512, hot_nnz=400, seed=0):
    """All the weight in one row: one chip's window dwarfs the rest."""
    rng = np.random.default_rng(seed)
    lengths = [hot_nnz] + [1] * (m - 1)
    row_ptr = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    cols = np.concatenate(
        [np.sort(rng.choice(n, size=int(ln), replace=False))
         for ln in lengths]).astype(np.int32)
    vals = rng.standard_normal(int(row_ptr[-1])).astype(np.float32)
    return CSRMatrix((m, n), row_ptr, cols, vals)


def _x(n, d, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((n, d)), jnp.float32)


# -- bit-identity ----------------------------------------------------------

@pytest.mark.parametrize("backend", FUSED)
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_xshard_bit_identical_to_replicated(backend, strategy):
    a = _mixed_csr(seed=2, m=56)
    x = _x(a.n, 20, seed=3)
    for chips in range(1, MAX_CHIPS + 1):
        y_rep = spmm(a, x, strategy=strategy, **FUSED[backend],
                     interpret=True, n_chips=chips,
                     x_sharding="replicated", cache=JitCache())
        y_row = spmm(a, x, strategy=strategy, **FUSED[backend],
                     interpret=True, n_chips=chips, x_sharding="rows",
                     cache=JitCache())
        assert np.array_equal(np.asarray(y_row), np.asarray(y_rep)), (
            strategy, chips)


@pytest.mark.parametrize("backend", FUSED)
def test_xshard_staged_bit_identical(backend):
    """x_sharding and staging compose: rows+dma == rows+resident ==
    replicated+resident == the unsharded fused dispatch, bit for bit."""
    a = random_csr(120, 96, density=0.06, family="powerlaw", seed=4)
    x = _x(a.n, 24, seed=5)
    y0 = spmm(a, x, **FUSED[backend], interpret=True, cache=JitCache())
    for staging in ("resident", "dma"):
        y = spmm(a, x, **FUSED[backend], interpret=True, staging=staging,
                 n_chips=MAX_CHIPS, x_sharding="rows", cache=JitCache())
        assert np.array_equal(np.asarray(y), np.asarray(y0)), staging


@pytest.mark.parametrize("backend", FUSED)
def test_xshard_gradients_bit_match_replicated(backend):
    """The custom VJP routes the backward through a transposed artifact
    that must inherit x_sharding (dY is then the row-sharded operand)."""
    a = _mixed_csr(seed=8)
    x = _x(a.n, 12, seed=9)
    vals = jnp.asarray(a.vals)
    c_rep = compile_spmm(a, 12, **FUSED[backend], interpret=True,
                         n_chips=MAX_CHIPS, x_sharding="replicated",
                         cache=JitCache())
    c_row = compile_spmm(a, 12, **FUSED[backend], interpret=True,
                         n_chips=MAX_CHIPS, x_sharding="rows",
                         cache=JitCache())

    def loss(c):
        return lambda v, xx: jnp.sum(jnp.tanh(c(v, xx)))

    gr = jax.grad(loss(c_rep), argnums=(0, 1))(vals, x)
    gd = jax.grad(loss(c_row), argnums=(0, 1))(vals, x)
    assert np.array_equal(np.asarray(gr[0]), np.asarray(gd[0]))
    assert np.array_equal(np.asarray(gr[1]), np.asarray(gd[1]))
    assert c_row._transpose is not None
    assert c_row._transpose.x_sharding == "rows"


# -- one pallas_call per chip ---------------------------------------------

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            # cond/switch park their sub-jaxprs in a `branches` TUPLE
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for vv in vs:
                inner = (vv if hasattr(vv, "eqns")
                         else getattr(vv, "jaxpr", None))
                if hasattr(inner, "eqns"):
                    yield from _iter_eqns(inner)


@pytest.mark.parametrize("backend,counter",
                         [("vpu", "bcsr_fused"),
                          ("pallas_bcsr", "bcsr_fused")])
def test_xshard_trace_is_one_pallas_call_per_chip(backend, counter):
    a = _mixed_csr(seed=10, m=56)
    x = _x(a.n, 16, seed=11)
    c = compile_spmm(a, 16, **FUSED[backend], interpret=True,
                     n_chips=MAX_CHIPS, x_sharding="rows",
                     cache=JitCache())
    jaxpr = jax.make_jaxpr(lambda v, xx: c(v, xx))(jnp.asarray(a.vals), x)
    eqns = list(_iter_eqns(jaxpr.jaxpr))
    shard_eqns = [e for e in eqns if e.primitive.name == "shard_map"]
    assert len(shard_eqns) == 1
    body = shard_eqns[0].params["jaxpr"]
    body = body if hasattr(body, "eqns") else body.jaxpr
    body_eqns = list(_iter_eqns(body))
    in_body = [e for e in body_eqns if e.primitive.name == "pallas_call"]
    assert len(in_body) == 1
    # the exchange is one all_to_all collective, inside the same body
    a2a = [e for e in body_eqns if e.primitive.name == "all_to_all"]
    assert len(a2a) == 1

    ops.reset_dispatch_counts()
    y = c(jnp.asarray(a.vals), x)
    jax.block_until_ready(y)
    assert ops.DISPATCH_COUNTS[counter] == MAX_CHIPS
    assert ops.DISPATCH_COUNTS[counter + "_xshard"] == MAX_CHIPS


def test_replicated_forward_counts_no_xshard_dispatch():
    a = _mixed_csr(seed=14)
    x = _x(a.n, 8, seed=15)
    c = compile_spmm(a, 8, backend="pallas_bcsr", interpret=True,
                     n_chips=MAX_CHIPS, x_sharding="replicated",
                     cache=JitCache())
    ops.reset_dispatch_counts()
    jax.block_until_ready(c(jnp.asarray(a.vals), x))
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == MAX_CHIPS
    assert ops.DISPATCH_COUNTS["bcsr_fused_xshard"] == 0


# -- specialization identity ----------------------------------------------

def test_jit_cache_keys_on_x_sharding():
    a = _mixed_csr(seed=16)
    cache = JitCache()
    c_rep = compile_spmm(a, 8, **FUSED["vpu"], interpret=True,
                         n_chips=1, x_sharding="replicated", cache=cache)
    c_row = compile_spmm(a, 8, **FUSED["vpu"], interpret=True,
                         n_chips=1, x_sharding="rows", cache=cache)
    assert c_rep is not c_row
    assert cache.stats()["entries"] == 2
    # "auto" under interpret mode resolves to replicated (the exchange
    # is pure overhead on an emulated mesh), same shape as staging
    assert compile_spmm(a, 8, **FUSED["vpu"], interpret=True,
                        n_chips=1, x_sharding="auto", cache=cache) is c_rep
    assert compile_spmm(a, 8, **FUSED["vpu"], interpret=True,
                        n_chips=1, cache=cache) is c_rep
    assert compile_spmm(a, 8, **FUSED["vpu"], interpret=True,
                        n_chips=1, x_sharding="rows", cache=cache) is c_row


def test_xshard_knob_contract():
    a = _mixed_csr(seed=17)
    # rows without a mesh: nothing owns the panels
    with pytest.raises(ValueError):
        compile_spmm(a, 8, **FUSED["vpu"], interpret=True,
                     x_sharding="rows", cache=JitCache())
    # the knob only exists on the fused dispatch
    with pytest.raises(ValueError):
        compile_spmm(a, 8, backend="ref", x_sharding="rows",
                     cache=JitCache())
    with pytest.raises(ValueError):
        compile_spmm(a, 8, **FUSED["vpu"], interpret=True,
                     n_chips=1, x_sharding="cols", cache=JitCache())
    # replicated/auto are accepted everywhere (they are the default)
    c = compile_spmm(a, 8, backend="ref", x_sharding="replicated",
                     cache=JitCache())
    assert c.x_sharding == "replicated"


# -- plan-time fetch tables ------------------------------------------------

@pytest.mark.parametrize("backend", FUSED)
def test_fetch_tables_cover_touched_panels(backend):
    a = _mixed_csr(seed=18, m=56, n=96)
    sw = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape, 16,
                                 n_chips=3,
                                 mxu_gain=0.0 if backend == "vpu" else 4.0,
                                 x_sharding="rows")
    bk = sw.bk
    assert sw.x_panels == -(-a.n // bk)
    assert sw.x_own_panels == -(-sw.x_panels // sw.n_chips)
    T = sw.x_local_panels
    for c in range(sw.n_chips):
        fetch = sw.x_fetch[c]
        assert np.all((fetch >= 0) & (fetch < sw.x_panels))
        assert fetch[0] == 0          # panel 0 is the padding sentinel
        # fetched panels are sorted-unique over the real prefix
        real = fetch[:len(set(fetch.tolist()))]
        assert np.all(np.diff(real) > 0) or real.size <= 1
        # the remapped column stream stays inside the local workspace:
        # VPU entries address rows < T*bk, MXU entries panels < T
        cols = sw.cols_flat[c]
        mxu_entry = np.zeros(cols.shape[0], bool)
        for tag, coff, L in zip(sw.blk_tag[c], sw.blk_coff[c],
                                sw.blk_L[c]):
            if tag == MXU_TAG:
                mxu_entry[coff:coff + L] = True
        assert np.all(cols[mxu_entry] < T)
        assert np.all(cols[~mxu_entry] < T * bk)
        # every remapped address points at the panel the original
        # structure touched: reconstruct via the fetch table
        # (exchange correctness is covered end-to-end by bit-identity)
        for src in range(sw.n_chips):
            row = sw.x_send[src, c]
            assert np.all((row >= 0) & (row < sw.x_own_panels))
        assert np.all(sw.x_recv[c] < sw.n_chips * sw.x_send.shape[2])


def test_replicated_workspace_has_no_fetch_tables():
    a = _mixed_csr(seed=19)
    sw = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape, 8,
                                 n_chips=2, mxu_gain=0.0,
                                 x_sharding="replicated")
    assert sw.x_fetch is None and sw.x_send is None and sw.x_recv is None
    assert sw.x_local_panels == 0


# -- per-chip DMA windows (hot-shard satellite) ----------------------------

def test_hot_shard_does_not_inflate_cold_chip_windows():
    """One all-nnz-in-one-row shard used to round EVERY chip's staged
    DMA window (and stream tail) up to the hot chip's span; now each
    chip's ring is sized from its own largest block."""
    a = _hot_csr()
    sw = build_sharded_workspace(a.row_ptr, a.col_indices, a.shape, 8,
                                 n_chips=4, strategy="nnz_split",
                                 mxu_gain=0.0)
    spans = np.asarray(sw.chip_span)
    assert spans.max() == sw.max_span
    assert spans.min() < spans.max()          # cold chips stay small
    # rectangular stream admits each chip's OWN window (not the max)
    assert np.all(
        sw.blk_off + spans[:, None] <= sw.gather_flat.shape[1])
    assert np.all(sw.blk_coff + np.asarray(sw.chip_cspan)[:, None]
                  <= sw.cols_flat.shape[1])
    # and the stream is tighter than the old global-window layout
    real = (sw.blk_off + sw.row_block
            * sw.blk_L.astype(np.int64)).max(axis=1)
    assert sw.gather_flat.shape[1] < int(real.max()) + 2 * sw.max_span


@pytest.mark.parametrize("backend", FUSED)
def test_hot_shard_staged_switch_still_one_call_per_chip(backend):
    """Heterogeneous windows lower as one specialized staged kernel per
    DISTINCT window behind a lax.switch — each chip still executes
    exactly one pallas_call, and the result stays bit-identical."""
    if MAX_CHIPS < 2:
        pytest.skip("needs a multi-device mesh")
    a = _hot_csr()
    x = _x(a.n, 8, seed=21)
    c = compile_spmm(a, 8, **FUSED[backend], interpret=True,
                     staging="dma", n_chips=MAX_CHIPS, cache=JitCache())
    sw = c.sharded_workspace
    n_windows = len(set(zip(sw.chip_span.tolist(),
                            sw.chip_cspan.tolist())))
    jaxpr = jax.make_jaxpr(lambda v, xx: c(v, xx))(jnp.asarray(a.vals), x)
    shard_eqns = [e for e in _iter_eqns(jaxpr.jaxpr)
                  if e.primitive.name == "shard_map"]
    body = shard_eqns[0].params["jaxpr"]
    body = body if hasattr(body, "eqns") else body.jaxpr
    in_body = [e for e in _iter_eqns(body)
               if e.primitive.name == "pallas_call"]
    # one specialized kernel per distinct window in the traced body;
    # each chip EXECUTES exactly one of them (switch on axis index)
    assert len(in_body) == n_windows
    y_ref = spmm(a, x, **FUSED[backend], interpret=True,
                 staging="resident", cache=JitCache())
    y = c(jnp.asarray(a.vals), x)
    assert np.array_equal(np.asarray(y), np.asarray(y_ref))


# -- 8-device acceptance ---------------------------------------------------

def test_acceptance_xshard_on_8_device_mesh():
    """ISSUE acceptance: X-sharded == replicated BIT-identical (forward
    and gradient) on a forced 8-chip host mesh for all three strategies
    x all-VPU and mixed plans, one pallas_call per chip, and per-chip VMEM
    windows that do not all scale with the hottest shard."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    code = textwrap.dedent("""
        import jax, numpy as np, jax.numpy as jnp
        assert len(jax.devices()) == 8
        from repro.core import compile_spmm, random_csr, spmm
        from repro.core.jit_cache import JitCache
        from repro.core.plan import STRATEGIES
        from repro.kernels import ops
        a = random_csr(128, 96, density=0.06, family="powerlaw", seed=21)
        x = jnp.asarray(np.random.default_rng(22)
                        .standard_normal((96, 16)), jnp.float32)
        vals = jnp.asarray(a.vals)
        for gain in (0.0, 4.0):  # all-VPU, and as the structure tags it
            for strategy in STRATEGIES:
                c0 = compile_spmm(a, 16, strategy=strategy,
                                  backend="pallas_bcsr", mxu_gain=gain,
                                  interpret=True, n_chips=8,
                                  x_sharding="replicated",
                                  cache=JitCache())
                c1 = compile_spmm(a, 16, strategy=strategy,
                                  backend="pallas_bcsr", mxu_gain=gain,
                                  interpret=True, n_chips=8,
                                  x_sharding="rows", cache=JitCache())
                ops.reset_dispatch_counts()
                y0, y1 = c0(vals, x), c1(vals, x)
                assert ops.DISPATCH_COUNTS["bcsr_fused_xshard"] == 8
                assert np.array_equal(np.asarray(y0), np.asarray(y1)), (
                    gain, strategy)
                lf = lambda c: (lambda v, xx:
                                jnp.sum(jnp.tanh(c(v, xx))))
                g0 = jax.grad(lf(c0), argnums=(0, 1))(vals, x)
                g1 = jax.grad(lf(c1), argnums=(0, 1))(vals, x)
                assert np.array_equal(np.asarray(g0[0]),
                                      np.asarray(g1[0]))
                assert np.array_equal(np.asarray(g0[1]),
                                      np.asarray(g1[1]))
        print("XSHARD-8DEV-OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "XSHARD-8DEV-OK" in out.stdout
