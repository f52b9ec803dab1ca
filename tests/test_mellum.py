"""Mellum2-12B-A2.5B at a reduced shape on the CPU: the serving
prefill and the train forward against a plain reference, its sliding
layers through the fused sparse-attention artifact (interpret mode),
dropless routing, the expert-parallel share, YaRN, and the vectorised
window mask."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import model_reference as ref
from repro.configs import get_config
from repro.kernels import ops
from repro.models import Model, layers, moe, sparse_attention, transformer
from repro.models.sparse_attention import sparse_attention_mask

MELLUM = get_config("mellum2-12b-a2.5b")
# one period, Mellum's router (64 experts, top-8), held experts 8..15
SMALL = dataclasses.replace(
    MELLUM, name="mellum2-small", num_layers=4, d_model=64, num_heads=4,
    num_kv_heads=2, head_dim=16, d_ff=32, vocab_size=256,
    sparse_attn_window=8, experts_held=(8, 8), dtype="float32")


def _tokens(B, S, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, SMALL.vocab_size, (B, S)), jnp.int32)


def _scaled_gap(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


def test_published_widths():
    c = MELLUM
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.head_dim, c.d_ff, c.vocab_size) == (28, 2304, 32, 4, 128,
                                                  896, 98304)
    assert c.pattern == ("sattn", "sattn", "sattn", "attn")
    assert (c.sparse_attn_window, c.sparse_attn_global) == (1024, 0)
    assert (c.num_experts, c.top_k, c.capacity_factor) == (64, 8, None)
    assert c.rope_theta == 5e5 and c.rope_yarn.factor == 16
    assert not c.qk_norm and not c.qkv_bias


@pytest.fixture(scope="module")
def small_params():
    return Model(SMALL).init(jax.random.PRNGKey(7))


@pytest.fixture
def fused_sattn(monkeypatch):
    """Off the chip ``backend="auto"`` resolves to the jnp ``ref``
    backend: steer the sattn slots onto the fused artifact (the chip's
    mixed ``pallas_bcsr`` plan, here in interpret mode)."""
    attend = sparse_attention.sparse_attend

    def fused(*args, **kwargs):
        return attend(*args, **dict(kwargs, backend="pallas_bcsr"))
    monkeypatch.setattr(sparse_attention, "sparse_attend", fused)


def test_prefill_and_train_forward_match_the_reference(small_params,
                                                       fused_sattn):
    B, S = 2, 32
    toks = _tokens(B, S, 1)
    want = ref.forward(SMALL, small_params, toks)
    ops.reset_dispatch_counts()
    with jax.default_matmul_precision("highest"):
        logits, caches = transformer.prefill(SMALL, small_params, toks,
                                             cache_len=S + 8)
        train, _ = transformer.forward_train(SMALL, small_params, toks,
                                             remat="none")
    # three sattn slots x B x H fused calls per entry point
    assert ops.DISPATCH_COUNTS["attn_fused"] == 2 * 3 * B * SMALL.num_heads
    assert _scaled_gap(logits, want) < 1e-5
    assert _scaled_gap(train, want) < 1e-5
    # sattn caches are full length, the prompt's K in the first S rows
    c = caches["slot0"]
    assert c["k"].shape == (1, B, S + 8, SMALL.num_kv_heads,
                            SMALL.head_dim)
    assert np.all(np.asarray(c["kpos"][0, :, :S]) == np.arange(S))
    assert np.all(np.asarray(c["kpos"][0, :, S:]) == 2 ** 30)


def test_prefill_through_the_derived_panel_matches_the_reference(
        small_params, fused_sattn):
    """A window wide enough that the mask's own panel is larger than
    8x8: the sattn slots run through it, and the prefill still agrees
    with the reference."""
    cfg = dataclasses.replace(SMALL, sparse_attn_window=64)
    B, S = 1, 128
    toks = _tokens(B, S, 2)
    want = ref.forward(cfg, small_params, toks)
    with jax.default_matmul_precision("highest"):
        logits, _ = transformer.prefill(cfg, small_params, toks,
                                        cache_len=S)
    _, art = sparse_attention._mask_and_artifact(
        S, cfg.head_dim, 64, 0, "pallas_bcsr", None)
    assert (art.bm, art.bk) == (16, 16) and not art._vpu
    assert _scaled_gap(logits, want) < 1e-5


def test_sattn_mask_build_is_counted():
    ops.reset_dispatch_counts()
    from repro.models.sparse_attention import sparse_attend
    q = jnp.ones((1, 40, 2, 16), jnp.float32)
    k = v = jnp.ones((1, 40, 1, 16), jnp.float32)
    out = sparse_attend(q, k, v, window=7)     # a new (S, window): a build
    assert out.shape == q.shape
    assert ops.BUILD_SECONDS["sattn_mask"] > 0


def _moe_params(E, D, F, seed):
    rng = np.random.default_rng(seed)

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.2, jnp.float32)
    return {"ln": jnp.ones((D,), jnp.float32), "router": w(D, E),
            "w_gate": w(E, D, F), "w_up": w(E, D, F), "w_down": w(E, F, D)}


def test_dropless_moe_matches_a_per_token_loop():
    E, k, D, F, B, S = 64, 8, 16, 8, 2, 48
    p = _moe_params(E, D, F, 3)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((B, S, D)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = moe.moe_ffn(p, x, num_experts=E, top_k=k,
                             capacity_factor=None)
    h = np.asarray(layers.rms_norm(x, p["ln"]), np.float64)
    P = {n: np.asarray(a, np.float64) for n, a in p.items()}
    want = np.array(x, np.float64)
    for b in range(B):
        for t in range(S):
            lg = h[b, t] @ P["router"]
            pr = np.exp(lg - lg.max())
            pr /= pr.sum()
            top = np.argsort(-pr)[:k]
            g = pr[top] / pr[top].sum()
            for e, ge in zip(top, g):
                a = h[b, t] @ P["w_gate"][e]
                act = a / (1 + np.exp(-a)) * (h[b, t] @ P["w_up"][e])
                want[b, t] += ge * (act @ P["w_down"][e])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)


def test_capacity_routing_still_drops_overflow():
    E, k, D, F, S = 4, 2, 8, 8, 32
    p = _moe_params(E, D, F, 5)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, S, D)),
                    jnp.float32)
    kw = dict(num_experts=E, top_k=k)
    tight, _ = moe.moe_ffn(p, x, capacity_factor=0.25, **kw)
    dropless, _ = moe.moe_ffn(p, x, capacity_factor=None, **kw)
    roomy, _ = moe.moe_ffn(p, x, capacity_factor=float(E), **kw)
    assert moe.moe_capacity(S, k, E, None) == S
    np.testing.assert_allclose(np.asarray(roomy), np.asarray(dropless),
                               rtol=1e-6, atol=1e-6)
    assert not np.allclose(np.asarray(tight), np.asarray(dropless))


def test_expert_shares_sum_to_the_uncut_layer():
    """8 chips of 8 experts each: the parts their shares add, with the
    residual counted once, give the layer that holds all 64."""
    E, k, D, F, B, S = 64, 8, 16, 8, 2, 24
    p = _moe_params(E, D, F, 8)
    x = jnp.asarray(np.random.default_rng(9).standard_normal((B, S, D)),
                    jnp.float32)
    kw = dict(num_experts=E, top_k=k, capacity_factor=None)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe.moe_ffn(p, x, **kw)
        parts = x
        for first in range(0, E, 8):
            share = dict(p, **{n: p[n][first:first + 8]
                               for n in ("w_gate", "w_up", "w_down")})
            y, _ = moe.moe_ffn(share, x, experts_held=(first, 8), **kw)
            parts = parts + (y - x)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("head_dim", [16, 128])
def test_yarn_frequencies_match_hf(head_dim):
    y = MELLUM.rope_yarn
    got, scale = layers.yarn_freqs(head_dim, MELLUM.rope_theta, y)
    want, want_scale = ref.hf_yarn_inv_freq(
        head_dim, MELLUM.rope_theta, y.factor, y.original_max_position,
        y.beta_fast, y.beta_slow, y.attention_factor)
    np.testing.assert_array_equal(got, want)
    assert scale == want_scale == y.attention_factor
    # without a given attention factor: HF's 0.1 ln(factor) + 1
    _, derived = layers.yarn_freqs(
        head_dim, MELLUM.rope_theta,
        dataclasses.replace(y, attention_factor=None))
    assert derived == pytest.approx(y.attention_factor, rel=1e-12)


def _loop_mask(S, window, num_global):
    """The row loop that the vectorised mask construction replaced."""
    g = min(num_global, S)
    row_ptr, cols = [0], []
    for i in range(S):
        lo = max(0, i - window + 1)
        if g and lo > g:
            cols.extend(list(range(g)) + list(range(lo, i + 1)))
        else:
            cols.extend(list(range(min(lo, g))) + list(range(lo, i + 1)))
        row_ptr.append(len(cols))
    return np.asarray(row_ptr, np.int64), np.asarray(cols, np.int32)


@pytest.mark.parametrize("S,window,num_global", [
    (20, 4, 3), (32, 8, 2), (96, 24, 4), (16, 1, 0), (16, 40, 0),
    (64, 8, 100), (1500, 1024, 0), (1024, 256, 64)])
def test_vectorised_mask_is_the_loop_mask(S, window, num_global):
    a = sparse_attention_mask(S, window, num_global)
    row_ptr, cols = _loop_mask(S, window, num_global)
    assert a.row_ptr.dtype == np.int64 and a.col_indices.dtype == np.int32
    np.testing.assert_array_equal(a.row_ptr, row_ptr)
    np.testing.assert_array_equal(a.col_indices, cols)
    assert a.nnz == cols.size
