"""Autotuner + jit-cache LRU suite (DESIGN.md §11).

The measurement hook is injectable, so every test runs on a
deterministic fake timer — no wall-clock flake:

  * a constant timer degenerates the winner to the best-PREDICTED
    candidate (the documented tie-break), so the search is reproducible;
  * a rigged timer that favors one specific config must crown exactly
    that config — runtime feedback really overrides the model;
  * the search memoizes: the second ``autotune=True`` compile is pure
    cache hits (zero new misses, the SAME artifact object) — the
    paper's Table IV amortization applied to the search itself.

The LRU tests pin the new capacity-bounded ``JitCache`` semantics the
autotuner relies on (it inserts one tune result + one artifact per
measured finalist).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (JitCache, TuneConfig, autotune_spmm,
                        autotune_spmm_with_result, compile_spmm,
                        default_candidates, random_csr, spmm)
from repro.core.autotune import TRIP_OVERHEAD_S, predict_seconds
from repro.core.plan import build_workspace
from repro.kernels import ops


# the fused backend with every block-row tagged VPU (ELL gather+FMA)
VPU = dict(backend="pallas_bcsr", mxu_gain=0.0)


@pytest.fixture
def a():
    return random_csr(48, 40, density=0.08, family="powerlaw", seed=7)


def _const_timer(compiled, vals, x):
    return 1.0


# ---------------------------------------------------------------------------
# search mechanics (deterministic fake timer)
# ---------------------------------------------------------------------------

def test_constant_timer_picks_best_predicted(a):
    compiled, res = autotune_spmm_with_result(
        a, 4, **VPU, interpret=True,
        measure=_const_timer, cache=JitCache())
    best_pred = min(res.measured_s, key=lambda c: res.predicted_s[c])
    assert res.config == best_pred
    assert res.best_measured_s == 1.0
    assert len(res.predicted_s) == len(default_candidates())
    assert 1 <= len(res.measured_s) <= 3          # top_k finalists
    # the artifact is the winner's compile and actually runs
    x = jnp.zeros((a.n, 4), jnp.float32)
    y = compiled(jnp.asarray(a.vals), x)
    assert y.shape == (a.m, 4)


def test_rigged_timer_overrides_prediction(a):
    """Runtime feedback wins: whatever the model ranked, the measured
    stage crowns the config the (fake) hardware liked."""
    cache = JitCache()
    # rig: make the LAST finalist (worst predicted among finalists)
    # measure fastest.  Identify it via a probe run's finalist set.
    _, probe_res = autotune_spmm_with_result(
        a, 4, **VPU, interpret=True,
        measure=_const_timer, cache=JitCache())
    finalists = sorted(probe_res.measured_s,
                       key=lambda c: probe_res.predicted_s[c])
    target = finalists[-1]
    calls = []

    def rigged(compiled, vals, x):
        calls.append(1)
        # compile order follows predicted rank, so the last measured
        # finalist is `target`
        return 0.5 if len(calls) == len(finalists) else 2.0

    _, res = autotune_spmm_with_result(
        a, 4, **VPU, interpret=True, measure=rigged,
        cache=cache)
    assert res.config == target
    assert res.best_measured_s == 0.5


def test_memoization_second_compile_is_pure_hit(a):
    cache = JitCache()
    c1 = compile_spmm(a, 4, **VPU, interpret=True,
                      autotune=True, measure=_const_timer, cache=cache)
    s1 = cache.stats()
    c2 = compile_spmm(a, 4, **VPU, interpret=True,
                      autotune=True, measure=_const_timer, cache=cache)
    s2 = cache.stats()
    assert c2 is c1                       # same memoized artifact
    assert s2["misses"] == s1["misses"]   # no new search, no new build
    assert s2["hits"] > s1["hits"]
    assert s2["evictions"] == 0


def test_spmm_autotune_matches_ref(a):
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((a.n, 4)), jnp.float32)
    y_ref = spmm(a, x, backend="ref", cache=JitCache())
    y = spmm(a, x, **VPU, interpret=True, autotune=True,
             measure=_const_timer, cache=JitCache())
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_autotune_records_tune_seconds(a):
    ops.reset_dispatch_counts()
    autotune_spmm(a, 4, **VPU, interpret=True,
                  measure=_const_timer, cache=JitCache())
    assert ops.BUILD_SECONDS["tune"] > 0
    assert ops.BUILD_SECONDS["plan"] > 0
    assert ops.BUILD_SECONDS["pack"] >= 0


def test_autotune_rejects_untunable_backend(a):
    with pytest.raises(ValueError, match="nothing to tune"):
        autotune_spmm(a, 4, backend="ref", interpret=True,
                      cache=JitCache())
    with pytest.raises(ValueError, match="at least one candidate"):
        autotune_spmm(a, 4, **VPU, interpret=True,
                      candidates=[], cache=JitCache())


def test_default_candidates_grid():
    cands = default_candidates(bm=8, bk=8, merge_thresholds=(0, 8, 32))
    assert len(cands) == 9                # 3 strategies x 3 thresholds
    assert len(set(cands)) == 9           # frozen dataclass, hashable
    kw = cands[0].compile_kwargs()
    assert set(kw) == {"strategy", "bm", "bk", "mxu_gain",
                       "merge_threshold", "staging"}


def test_predict_seconds_rewards_merging(a):
    """The analytic model's per-trip overhead term makes a CGCM-merged
    plan rank at or above the unmerged plan of the same strategy on a
    powerlaw instance (fewer grid steps, same streamed bytes)."""
    c0 = TuneConfig(merge_threshold=0, mxu_gain=0.0)
    c1 = TuneConfig(merge_threshold=32, mxu_gain=0.0)
    p0 = predict_seconds(a, 4, c0)
    p1 = predict_seconds(a, 4, c1)
    assert p0 > 0 and p1 > 0
    ws0 = build_workspace(a.row_ptr, a.col_indices, a.shape, 4,
                          mxu_gain=0.0, merge_threshold=0)
    ws1 = build_workspace(a.row_ptr, a.col_indices, a.shape, 4,
                          mxu_gain=0.0, merge_threshold=32)
    assert ws1.num_trips < ws0.num_blocks
    assert p1 < p0
    # the saving is dominated by the per-trip term (the streamed-bytes
    # terms shift only by the merged window's tail padding)
    assert p0 - p1 > 0.5 * (ws0.num_trips - ws1.num_trips) * TRIP_OVERHEAD_S


# ---------------------------------------------------------------------------
# JitCache LRU bound
# ---------------------------------------------------------------------------

def test_cache_lru_eviction_order():
    cache = JitCache(capacity=2)
    cache.get_or_build(("a",), lambda: 1)
    cache.get_or_build(("b",), lambda: 2)
    cache.get_or_build(("a",), lambda: 1)      # hit: promote a to MRU
    cache.get_or_build(("c",), lambda: 3)      # evicts b (LRU)
    assert cache.stats()["evictions"] == 1
    assert cache.get_or_build(("a",), lambda: -1) == 1   # still cached
    calls = []
    assert cache.get_or_build(("b",), lambda: calls.append(1) or 2) == 2
    assert calls == [1]                        # b was really evicted


def test_cache_capacity_bound_and_stats():
    cache = JitCache(capacity=3)
    for i in range(10):
        cache.get_or_build(("k", i), lambda i=i: i)
    st = cache.stats()
    assert st["entries"] == 3
    assert st["capacity"] == 3
    assert st["misses"] == 10
    assert st["evictions"] == 7
    cache.clear()
    st = cache.stats()
    assert st["entries"] == st["hits"] == st["evictions"] == 0


def test_cache_unbounded_default_and_invalid_capacity():
    cache = JitCache()
    for i in range(50):
        cache.get_or_build(("k", i), lambda i=i: i)
    assert cache.stats()["entries"] == 50
    assert cache.stats()["capacity"] is None
    assert cache.stats()["evictions"] == 0
    with pytest.raises(ValueError):
        JitCache(capacity=0)


def test_cache_bounded_autotune_evicts_but_stays_correct(a):
    """A tiny cache forces the tune result itself out; the search just
    reruns (correctness never depends on residency)."""
    cache = JitCache(capacity=2)
    c1 = autotune_spmm(a, 4, **VPU, interpret=True,
                       measure=_const_timer, cache=cache)
    assert cache.stats()["evictions"] > 0
    c2 = autotune_spmm(a, 4, **VPU, interpret=True,
                       measure=_const_timer, cache=cache)
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((a.n, 4)), jnp.float32)
    v = jnp.asarray(a.vals)
    assert np.array_equal(np.asarray(c1(v, x)), np.asarray(c2(v, x)))
