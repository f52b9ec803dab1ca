"""Acceptance suite for the fused sparse-attention sandwich
(DESIGN.md §13): SDDMM score -> in-register segment softmax -> S·V
through the SpMM descriptor stream, ONE pallas_call per chip, the score
matrix never materialized in HBM.

Pinned here:

  * numerics: fused == dense masked-softmax oracle (f64 numpy) across
    backends, stagings, strategies — including weighted masks
    (p ∝ w·exp(z)), empty rows (output 0), and multi-trip block-rows
    (the running-max rescale across trips must keep them exact),
  * gradients: the custom-VJP backward (jnp reference recompute)
    matches the ref backend's gradient for q, k, v AND the mask vals,
  * CGCM merging and sharding are bit-pure re-partitionings,
  * the Table IV invariant: exactly one pallas_call per chip per
    forward, on the traced jaxpr and in DISPATCH_COUNTS,
  * the jit-cache key separates every resolved knob,
  * the model-layer bridge: sparse_self_attention_layer == dense GQA
    attention with the equivalent window+global mask,
  * the MXU panel derived from the mask (DESIGN.md §13.4): every edge
    matches the reference, the rule's choices, the cache key, and an
    all-MXU plan's kernel without the VPU branch.
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

from repro.core import (CSRMatrix, compile_sparse_attention, random_csr,
                        sparse_attention)
from repro.core.jit_cache import JitCache
from repro.core.plan import STRATEGIES
from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[1]
N_DEV = len(jax.devices())
MAX_CHIPS = min(N_DEV, 4)
# the fused backend with every block-row tagged VPU, and as the mask
# tags it; parametrized by key
FUSED = {"vpu": dict(backend="pallas_bcsr", mxu_gain=0.0),
         "pallas_bcsr": dict(backend="pallas_bcsr")}


def _dense_oracle(a, vals, q, k, v):
    """f64 numpy oracle: softmax over present entries with weights w —
    p ∝ w·exp(z), empty rows -> 0."""
    m, n = a.shape
    rows = np.repeat(np.arange(m), np.diff(a.row_ptr))
    W = np.zeros((m, n), np.float64)
    W[rows, a.col_indices] = np.asarray(vals, np.float64)
    scale = q.shape[1] ** -0.5
    z = (np.asarray(q, np.float64) @ np.asarray(k, np.float64).T) * scale
    zm = np.where(W > 0, z, -np.inf)
    zmax = np.max(zm, axis=1, initial=-np.inf)
    zmax = np.where(np.isfinite(zmax), zmax, 0.0)
    zc = np.where(W > 0, z, zmax[:, None])   # inert where absent
    p = W * np.exp(zc - zmax[:, None])
    denom = p.sum(axis=1)
    out = p @ np.asarray(v, np.float64)
    return out / np.where(denom > 0, denom, 1.0)[:, None]


def _qkv(m, n, dh, dv, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((m, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((n, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n, dv)), jnp.float32)
    return q, k, v


def _mask(m=48, n=40, seed=0, density=0.15, family="powerlaw",
          weighted=True):
    a = random_csr(m, n, density=density, family=family, seed=seed)
    rng = np.random.default_rng(seed + 1)
    vals = (rng.uniform(0.2, 2.0, a.nnz).astype(np.float32) if weighted
            else np.ones(a.nnz, np.float32))
    return CSRMatrix(a.shape, a.row_ptr, a.col_indices, jnp.asarray(vals))


# ---------------------------------------------------------------------------
# Numerics vs the dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", FUSED)
@pytest.mark.parametrize("staging", ("resident", "dma"))
def test_fused_matches_dense_oracle(backend, staging):
    a = _mask(seed=3)
    q, k, v = _qkv(a.m, a.n, 12, 20, seed=4)
    want = _dense_oracle(a, a.vals, q, k, v)
    for strategy in STRATEGIES:
        c = compile_sparse_attention(
            a, 12, 20, strategy=strategy, **FUSED[backend],
            interpret=True, staging=staging, cache=JitCache())
        got = np.asarray(c(jnp.asarray(a.vals), q, k, v))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=f"{backend}/{staging}/"
                                           f"{strategy}")


@pytest.mark.parametrize("backend", FUSED)
def test_multi_trip_rows_stay_exact(backend):
    """A fully-dense heavy row spans many descriptor trips; the running
    max must rescale the accumulator so the result matches the oracle
    as tightly as a single-trip row does."""
    rng = np.random.default_rng(7)
    n = 64
    dense = np.zeros((24, n), np.float32)
    dense[0] = rng.uniform(0.2, 2.0, n)               # heavy: all of n
    dense[1, :40] = rng.uniform(0.2, 2.0, 40)
    for i in range(2, 24):
        cols = rng.choice(n, size=rng.integers(1, 5), replace=False)
        dense[i, cols] = rng.uniform(0.2, 2.0, cols.size)
    a = CSRMatrix.from_dense(dense)
    # large logits stress the rescale: scale q up so exp() would
    # overflow without the running max
    q, k, v = _qkv(a.m, a.n, 8, 8, seed=8)
    q = q * 12.0
    want = _dense_oracle(a, a.vals, q, k, v)
    got = np.asarray(sparse_attention(a, q, k, v, **FUSED[backend],
                                      interpret=True, cache=JitCache()))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_empty_rows_produce_zero_output():
    row_ptr = np.array([0, 2, 2, 3, 3], np.int64)
    cols = np.array([0, 3, 1], np.int32)
    a = CSRMatrix((4, 5), row_ptr, cols, jnp.ones((3,), jnp.float32))
    q, k, v = _qkv(4, 5, 6, 6, seed=9)
    y = np.asarray(sparse_attention(a, q, k, v, **FUSED["vpu"],
                                    interpret=True, cache=JitCache()))
    assert np.all(y[1] == 0) and np.all(y[3] == 0)
    np.testing.assert_allclose(y, _dense_oracle(a, a.vals, q, k, v),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", FUSED)
def test_gradients_match_ref_backend(backend):
    a = _mask(seed=11)
    q, k, v = _qkv(a.m, a.n, 8, 12, seed=12)
    vals = jnp.asarray(a.vals)

    def loss(c):
        def f(w, qq, kk, vv):
            return jnp.sum(jnp.sin(c(w, qq, kk, vv)))
        return jax.grad(f, argnums=(0, 1, 2, 3))(vals, q, k, v)

    g_fused = loss(compile_sparse_attention(
        a, 8, 12, **FUSED[backend], interpret=True, cache=JitCache()))
    g_ref = loss(compile_sparse_attention(
        a, 8, 12, backend="ref", cache=JitCache()))
    for gf, gr, name in zip(g_fused, g_ref, ("vals", "q", "k", "v")):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("backend", FUSED)
def test_merged_bit_matches_unmerged(backend):
    a = _mask(m=64, n=48, seed=13, density=0.08)
    q, k, v = _qkv(a.m, a.n, 8, 8, seed=14)
    y0 = sparse_attention(a, q, k, v, **FUSED[backend], interpret=True,
                          merge_threshold=0, cache=JitCache())
    y1 = sparse_attention(a, q, k, v, **FUSED[backend], interpret=True,
                          merge_threshold=16, cache=JitCache())
    assert np.array_equal(np.asarray(y0), np.asarray(y1))


@pytest.mark.parametrize("backend", FUSED)
@pytest.mark.parametrize("staging", ("resident", "dma"))
def test_sharded_bit_matches_single_chip(backend, staging):
    a = _mask(m=64, n=48, seed=15, density=0.1)
    q, k, v = _qkv(a.m, a.n, 8, 8, seed=16)
    y0 = sparse_attention(a, q, k, v, **FUSED[backend], interpret=True,
                          staging=staging, cache=JitCache())
    for chips in range(1, MAX_CHIPS + 1):
        y = sparse_attention(a, q, k, v, **FUSED[backend],
                             interpret=True, staging=staging,
                             n_chips=chips, cache=JitCache())
        assert np.array_equal(np.asarray(y0), np.asarray(y)), \
            (chips, backend, staging)


# ---------------------------------------------------------------------------
# The Table IV invariant: one pallas_call per chip
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            inner = val if hasattr(val, "eqns") else getattr(val, "jaxpr",
                                                             None)
            if hasattr(inner, "eqns"):
                yield from _iter_eqns(inner)


@pytest.mark.parametrize("backend", FUSED)
@pytest.mark.parametrize("staging", ("resident", "dma"))
def test_forward_is_one_pallas_call(backend, staging):
    a = _mask(seed=17)
    q, k, v = _qkv(a.m, a.n, 8, 8, seed=18)
    c = compile_sparse_attention(a, 8, 8, **FUSED[backend],
                                 interpret=True, staging=staging,
                                 cache=JitCache())
    jaxpr = jax.make_jaxpr(
        lambda w, qq, kk, vv: c(w, qq, kk, vv))(
        jnp.asarray(a.vals), q, k, v)
    pallas = [e for e in _iter_eqns(jaxpr.jaxpr)
              if e.primitive.name == "pallas_call"]
    assert len(pallas) == 1

    ops.reset_dispatch_counts()
    y = c(jnp.asarray(a.vals), q, k, v)
    jax.block_until_ready(y)
    assert ops.DISPATCH_COUNTS["attn_fused"] == 1
    assert ops.DISPATCH_COUNTS["attn_fused_dma"] == (
        1 if staging == "dma" else 0)


@pytest.mark.skipif(N_DEV < 2, reason="single-device host")
@pytest.mark.parametrize("backend", FUSED)
def test_sharded_forward_is_one_pallas_call_per_chip(backend):
    chips = MAX_CHIPS
    a = _mask(m=64, n=48, seed=19, density=0.1)
    q, k, v = _qkv(a.m, a.n, 8, 8, seed=20)
    c = compile_sparse_attention(a, 8, 8, **FUSED[backend],
                                 interpret=True, n_chips=chips,
                                 cache=JitCache())
    jaxpr = jax.make_jaxpr(
        lambda w, qq, kk, vv: c(w, qq, kk, vv))(
        jnp.asarray(a.vals), q, k, v)
    eqns = list(_iter_eqns(jaxpr.jaxpr))
    shard_eqns = [e for e in eqns if e.primitive.name == "shard_map"]
    assert len(shard_eqns) == 1
    body = shard_eqns[0].params["jaxpr"]
    body = body if hasattr(body, "eqns") else body.jaxpr
    pallas = [e for e in _iter_eqns(body)
              if e.primitive.name == "pallas_call"]
    assert len(pallas) == 1   # one per chip inside the mapped body

    ops.reset_dispatch_counts()
    y = c(jnp.asarray(a.vals), q, k, v)
    jax.block_until_ready(y)
    assert ops.DISPATCH_COUNTS["attn_fused"] == chips
    assert ops.DISPATCH_COUNTS["attn_fused_sharded"] == 1


def test_acceptance_on_8_device_mesh():
    """ISSUE acceptance on a forced 8-device host mesh: sharded fused
    == single-chip fused bit-identical, 8 dispatches per forward, and
    both match the ref oracle."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(ROOT / "src")
    code = textwrap.dedent("""
        import jax, numpy as np, jax.numpy as jnp
        assert len(jax.devices()) == 8
        from repro.core import CSRMatrix, random_csr, sparse_attention
        from repro.core.jit_cache import JitCache
        from repro.kernels import ops
        s = random_csr(96, 64, density=0.08, family="powerlaw", seed=0)
        rng = np.random.default_rng(1)
        # mask weights are non-negative by contract (p ∝ w·exp(z))
        a = CSRMatrix(s.shape, s.row_ptr, s.col_indices,
                      jnp.asarray(rng.uniform(0.2, 2.0, s.nnz),
                                  jnp.float32))
        q = jnp.asarray(rng.standard_normal((96, 8)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
        y_ref = sparse_attention(a, q, k, v, backend="ref",
                                 cache=JitCache())
        for gain in (0.0, 4.0):      # all-VPU, and as the mask tags it
            y0 = sparse_attention(a, q, k, v, backend="pallas_bcsr",
                                  mxu_gain=gain, interpret=True,
                                  cache=JitCache())
            ops.reset_dispatch_counts()
            y8 = sparse_attention(a, q, k, v, backend="pallas_bcsr",
                                  mxu_gain=gain, interpret=True,
                                  n_chips=8, cache=JitCache())
            assert ops.DISPATCH_COUNTS["attn_fused"] == 8, gain
            assert np.array_equal(np.asarray(y0), np.asarray(y8)), gain
            np.testing.assert_allclose(np.asarray(y8), np.asarray(y_ref),
                                       rtol=1e-4, atol=1e-4)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ---------------------------------------------------------------------------
# Cache-key discipline
# ---------------------------------------------------------------------------

def test_jit_cache_key_separates_knobs():
    a = _mask(seed=21)
    cache = JitCache()
    c0 = compile_sparse_attention(a, 8, **FUSED["vpu"],
                                  interpret=True, cache=cache)
    assert compile_sparse_attention(a, 8, **FUSED["vpu"],
                                    interpret=True, cache=cache) is c0
    distinct = [
        compile_sparse_attention(a, 8, **FUSED["vpu"],
                                 interpret=True, staging="dma",
                                 cache=cache),
        compile_sparse_attention(a, 8, **FUSED["vpu"],
                                 interpret=True, sm_scale=1.0,
                                 cache=cache),
        compile_sparse_attention(a, 8, 16, **FUSED["vpu"],
                                 interpret=True, cache=cache),
        compile_sparse_attention(a, 8, **FUSED["vpu"],
                                 interpret=True, merge_threshold=16,
                                 cache=cache),
    ]
    assert all(c is not c0 for c in distinct)
    assert len({id(c) for c in distinct}) == len(distinct)


# ---------------------------------------------------------------------------
# Model-layer bridge
# ---------------------------------------------------------------------------

def test_sparse_attention_mask_structure():
    from repro.models.sparse_attention import sparse_attention_mask
    S, w, g = 20, 4, 3
    a = sparse_attention_mask(S, w, g)
    assert a.shape == (S, S)
    dense = np.asarray(a.to_dense())
    for i in range(S):
        for j in range(S):
            want = j <= i and (i - j < w or j < g)
            assert bool(dense[i, j] != 0) == want, (i, j)


def test_sattn_layer_matches_dense_masked_attention():
    """The fused sandwich through the model layer == dense GQA attention
    with the equivalent causal window+global mask (same softmax over
    the same present entries)."""
    from repro.models import layers
    from repro.models.sparse_attention import sparse_self_attention_layer
    B, S, D, H, KV, hd = 2, 16, 32, 4, 2, 8
    w, g = 6, 2
    rng = np.random.default_rng(30)
    x = jnp.asarray(rng.standard_normal((B, S, D)) * 0.3, jnp.float32)
    p = {
        "ln": jnp.ones((D,), jnp.float32),
        "wq": jnp.asarray(rng.standard_normal((D, H, hd)) * 0.1,
                          jnp.float32),
        "wk": jnp.asarray(rng.standard_normal((D, KV, hd)) * 0.1,
                          jnp.float32),
        "wv": jnp.asarray(rng.standard_normal((D, KV, hd)) * 0.1,
                          jnp.float32),
        "wo": jnp.asarray(rng.standard_normal((H, hd, D)) * 0.1,
                          jnp.float32),
    }
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    got = sparse_self_attention_layer(
        p, x, positions=positions, head_dim=hd, num_heads=H,
        num_kv_heads=KV, window=w, num_global=g, rope_theta=1e4)

    h = layers.rms_norm(x, p["ln"], 1e-5)
    q, k, v = layers.attn_project_qkv(p, h, H, KV, hd, qk_norm=False,
                                      norm_eps=1e-5)
    q = layers.apply_rope(q, positions, 1e4)
    k = layers.apply_rope(k, positions, 1e4)
    out = layers.gqa_attention(q, k, v, q_positions=positions,
                               kv_positions=positions, causal=True,
                               window=w, num_global=g)
    want = x + jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mixed", (False, True))
def test_split_blocks_keep_the_softmax_exact(mixed, pack_with_window):
    """Blocks split into piece trips carry the softmax state (acc, m, l)
    from trip to trip: at a tiny staging window both lowerings
    reproduce the unsplit plan bit for bit."""
    from repro.core import (build_fused_workspace, build_mixed_plan,
                            workspace_row_map)
    a = _mask(m=40, n=48, density=0.4, seed=3)
    q, k, v = _qkv(40, 48, 16, 16, seed=4)
    plan = build_mixed_plan(a.row_ptr, a.col_indices, a.shape, 16,
                            mxu_gain=4.0 if mixed else 0.0)

    def run(ws, staging):
        vals = jnp.concatenate([a.vals, jnp.zeros((1,), jnp.float32)])
        q_ext = jnp.pad(q * 16 ** -0.5, ((0, 1), (0, 112)))
        row_map = jnp.asarray(workspace_row_map(
            ws.inv_perm, ws.ws_rows, ws.blk_cont,
            ws.merge_width * ws.row_block))
        y = ops.attn_fused_op(
            jnp.asarray(ws.blk_tag), jnp.asarray(ws.blk_off),
            jnp.asarray(ws.blk_coff), jnp.asarray(ws.blk_L),
            jnp.asarray(ws.cols_flat), vals[jnp.asarray(ws.gather_flat)],
            q_ext[row_map], jnp.pad(k, ((0, 0), (0, 112))),
            jnp.pad(v, ((0, 0), (0, 112))), jnp.asarray(ws.blk_cont),
            bk=ws.bk, mw=ws.merge_width, interpret=True, staging=staging,
            span=ws.max_span, cspan=ws.max_cspan)
        return np.asarray(y[jnp.asarray(ws.inv_perm), :16])

    ws0 = build_fused_workspace(plan)
    ws = pack_with_window(plan, 32)
    assert ws0.blk_cont.sum() == 0 < ws.blk_cont.sum()
    y0 = run(ws0, "resident")
    np.testing.assert_allclose(y0, _dense_oracle(a, a.vals, q, k, v),
                               rtol=1e-4, atol=1e-4)
    for staging in ("resident", "dma"):
        assert np.array_equal(run(ws, staging), y0)


@pytest.mark.parametrize("sharded", (False, True))
def test_split_blocks_through_compile_sparse_attention(sharded,
                                                       monkeypatch):
    """The artifact stages Q into every piece of a split block (the
    row map repeats the last piece's rows) and matches its unsplit twin
    bit for bit, single-chip and chip-stacked."""
    from repro import platform
    from repro.core import plan as plan_mod
    a = _mask(m=40, n=48, density=0.4, seed=5)
    q, k, v = _qkv(40, 48, 16, 16, seed=6)

    def run():
        art = compile_sparse_attention(
            a, 16, backend="pallas_bcsr", interpret=True, staging="dma",
            n_chips=MAX_CHIPS if sharded else None, cache=JitCache())
        consts = art._sharded if sharded else art._fused
        return np.asarray(art(a.vals, q, k, v)), int(consts.cont.sum())

    y0, pieces0 = run()
    monkeypatch.setattr(plan_mod, "stage_limits", lambda: platform.StageLimits(
        window=32, descs=platform.stage_limits().descs))
    y, pieces = run()
    assert pieces0 == 0 < pieces
    assert np.array_equal(y, y0)


@pytest.mark.parametrize("backend", FUSED)
@pytest.mark.parametrize("staging", ("resident", "dma"))
def test_live_lane_staging_matches_the_plain_gather(backend, staging):
    """On a window+global mask the MXU plan stages its slot values from
    the live lanes of its lane-padded panels: the output equals the
    same artifact fed the whole element gather ``concat(vals,[0])
    [gather_flat]`` bit for bit, and the oracle within tolerance."""
    import dataclasses
    from repro.core.spmm import _SlotValues, _tiles
    from repro.models.sparse_attention import sparse_attention_mask
    a = sparse_attention_mask(96, window=24, num_global=4)
    q, k, v = _qkv(a.m, a.n, 16, 16, seed=22)
    # the 8x8 layout: 120 of each panel's 128 lanes are padding
    art = compile_sparse_attention(a, 16, **FUSED[backend], bm=8, bk=8,
                                   interpret=True, staging=staging,
                                   cache=JitCache())
    vals = jnp.asarray(a.vals)
    y = np.asarray(art(vals, q, k, v))
    ws = art.workspace
    compact = backend == "pallas_bcsr"
    assert (art._fused.vals.lanes is not None) == compact
    assert (art.vals_gather_elems < art.vals_slots) == compact
    g = _tiles(ws.gather_flat, a.nnz)
    art._fused = dataclasses.replace(art._fused, vals=_SlotValues(
        gather=jnp.asarray(g.astype(np.int32)), shape=g.shape))
    assert np.array_equal(np.asarray(art(vals, q, k, v)), y)
    np.testing.assert_allclose(y, _dense_oracle(a, a.vals, q, k, v),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# The MXU panel derived from the mask (DESIGN.md §13.4)
# ---------------------------------------------------------------------------

def _longformer_mask(S, window, global_tokens):
    """The benchmark's two-sided window + global-token mask."""
    import importlib.util
    path = ROOT / "chipbench" / "gen" / "longformer_mask.py"
    spec = importlib.util.spec_from_file_location("longformer_mask", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.generate({"seq_len": S, "attention_window": window,
                         "global_tokens": global_tokens})


PANEL_MASKS = {
    "causal_window": lambda: _window(128, 64, 0),
    "window_global": lambda: _window(128, 48, 8),
    "ragged_random": lambda: _mask(m=128, n=128, seed=31, density=0.06),
}


def _window(S, window, num_global):
    from repro.models.sparse_attention import sparse_attention_mask
    return sparse_attention_mask(S, window, num_global)


@pytest.mark.parametrize("mask", sorted(PANEL_MASKS))
@pytest.mark.parametrize("b", (8, 16, 32, 64, 128))
@pytest.mark.parametrize("staging", ("resident", "dma"))
def test_every_panel_edge_matches_the_reference(mask, b, staging):
    """A pinned square panel of each edge, on masks that go all-MXU and
    on a ragged one whose block-rows stay VPU: the fused forward
    matches the artifact's jnp reference."""
    a = PANEL_MASKS[mask]()
    q, k, v = _qkv(a.m, a.n, 16, 16, seed=b)
    c = compile_sparse_attention(a, 16, backend="pallas_bcsr", bm=b, bk=b,
                                 interpret=True, staging=staging,
                                 cache=JitCache())
    assert (c.bm, c.bk) == (b, b)
    vals = jnp.asarray(a.vals)
    np.testing.assert_allclose(np.asarray(c(vals, q, k, v)),
                               np.asarray(c._ref_forward(vals, q, k, v)),
                               rtol=1e-5, atol=1e-5)


def test_panel_edge_follows_the_mask():
    """The rule on the benchmark's two masks at full size and at a
    quarter of the sequence, on ragged random masks, and with more
    chips than block-rows."""
    from repro.core.plan import choose_panel_edge

    def edge(mask, **kw):
        rp, cols, shape = mask
        return choose_panel_edge(rp, cols, shape, **kw)

    def csr(a):
        return a.row_ptr, a.col_indices, a.shape

    for S in (4096, 1024):
        assert edge(csr(_window(S, 1024, 0))) == 128          # Mellum
        assert edge(_longformer_mask(S, 512, 64)) == 64       # Longformer
    for family in ("powerlaw", "uniform", "banded"):
        a = random_csr(256, 256, density=0.05, family=family, seed=1)
        assert edge(csr(a)) == 8, family
    assert edge(csr(PANEL_MASKS["ragged_random"]())) == 8
    narrow = csr(_window(256, 128, 0))
    assert edge(narrow) == 32
    assert edge(narrow, chips=16) == 16      # 8 block-rows of 32


def test_derived_panel_through_compile_sparse_attention():
    """With neither bm nor bk given the mixed backend takes the mask's
    panel, which joins the cache key; an explicit panel is honoured,
    and the artifact counts the panels one call multiplies."""
    from repro.core.plan import MXU_TAG
    a = _window(256, 64, 0)
    cache = JitCache()
    c = compile_sparse_attention(a, 16, backend="pallas_bcsr",
                                 interpret=True, cache=cache)
    assert (c.bm, c.bk) == (16, 16) and not c._vpu
    assert compile_sparse_attention(a, 16, backend="pallas_bcsr", bm=16,
                                    bk=16, interpret=True,
                                    cache=cache) is c
    c8 = compile_sparse_attention(a, 16, backend="pallas_bcsr", bm=8,
                                  interpret=True, cache=cache)
    assert (c8.bm, c8.bk) == (8, 8) and c8 is not c
    ws = c.workspace
    assert c.mxu_panels == int(ws.blk_L[ws.blk_tag == MXU_TAG].sum())
    assert c.panel_fill == pytest.approx(a.nnz / (c.mxu_panels * 16 * 16))
    assert c8.mxu_panels > c.mxu_panels and c8.panel_fill > c.panel_fill
    vpu = compile_sparse_attention(a, 16, **FUSED["vpu"], interpret=True,
                                   cache=cache)
    assert (vpu.bm, vpu.bk, vpu.mxu_panels) == (8, 8, 0)
    ref = compile_sparse_attention(a, 16, backend="ref", cache=cache)
    assert ref.mxu_panels is None and ref.panel_fill is None
    q, k, v = _qkv(a.m, a.n, 16, 16, seed=33)
    vals = jnp.asarray(a.vals)
    y = np.asarray(c(vals, q, k, v))
    np.testing.assert_allclose(y, np.asarray(c8(vals, q, k, v)),
                               rtol=1e-5, atol=1e-5)
    # the chip-stacked lowering takes the same panel, bit for bit
    cs = compile_sparse_attention(a, 16, backend="pallas_bcsr",
                                  interpret=True, n_chips=MAX_CHIPS,
                                  cache=cache)
    assert (cs.bm, cs.mxu_panels, cs._vpu) == (16, c.mxu_panels, False)
    assert np.array_equal(np.asarray(cs(vals, q, k, v)), y)


def _loops(jaxpr) -> int:
    """``while`` loops in a jaxpr, through every nested jaxpr."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "while"
        for val in eqn.params.values():
            for inner in (val if isinstance(val, (tuple, list)) else (val,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    n += _loops(inner)
    return n


@pytest.mark.parametrize("staging", ("resident", "dma"))
def test_all_mxu_kernel_holds_no_vpu_branch(staging):
    """A plan of MXU trips only lowers one trip loop per member; a plan
    with VPU trips lowers the VPU loop beside it."""
    def kernel_loops(a):
        c = compile_sparse_attention(a, 16, backend="pallas_bcsr",
                                     interpret=True, staging=staging,
                                     cache=JitCache())
        q, k, v = _qkv(a.m, a.n, 16, 16)
        jaxpr = jax.make_jaxpr(c)(jnp.asarray(a.vals), q, k, v)
        (call,) = [e for e in _iter_eqns(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
        return c, _loops(call.params["jaxpr"])

    c, loops = kernel_loops(PANEL_MASKS["causal_window"]())
    assert not c._vpu and loops == c._fused.merge_width
    c, loops = kernel_loops(PANEL_MASKS["ragged_random"]())
    assert c._vpu and loops == 2 * c._fused.merge_width


def test_chip_smoke_logs_each_attention_cells_panel():
    """``chip_smoke.py`` names the panel each attention cell of the chip
    benchmark resolves to, with its panels per call and their fill."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    want = {"mellum2.prefill_4k": (128, 252), "longformer.attn_fwd": (64, 674)}
    for cell, (mask, dh) in smoke.benchmark_attention_masks().items():
        c = compile_sparse_attention(mask, dh, backend="pallas_bcsr",
                                     interpret=True, staging="dma",
                                     cache=JitCache())
        b, panels = want[cell]
        assert (c.bm, c.bk, c.mxu_panels) == (b, b, panels), cell
        line = smoke.describe_panel(c)
        assert line.startswith(f"panel {b}x{b}: {panels} MXU panels per "
                               f"call, fill {c.panel_fill:.4f}"), line
