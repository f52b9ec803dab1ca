"""Serving tier (DESIGN.md §12): batched == solo bit-identity, one
fused dispatch per batch, cross-request cache behavior (zero rebuild on
the second request, single-flight under concurrent first requests,
clear-vs-inflight invalidation), and the generate-driver regressions
(sampling with rng=None, no per-call retrace)."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import (CompiledBatchedSpmm, compile_batched_spmm,
                        random_csr, spmm)
from repro.core.jit_cache import JitCache
from repro.kernels import ops
from repro.launch.serve import (SpmmRequest, SpmmServer, _serve_callables,
                                d_bucket, generate)
from repro.models import Model

# the fused backend with every block-row tagged VPU, and as the
# structure tags it; parametrized by key
FUSED = {"vpu": dict(backend="pallas_bcsr", mxu_gain=0.0),
         "pallas_bcsr": dict(backend="pallas_bcsr")}
STAGINGS = ("resident", "dma")


def _tenants(seed=0):
    """Mixed shapes/families, mixed d within one bucket."""
    rng = np.random.default_rng(seed)
    mats = [random_csr(48, 64, density=0.08, family="powerlaw", seed=11),
            random_csr(64, 48, density=0.06, family="uniform", seed=12),
            random_csr(40, 40, density=0.12, family="banded", seed=13)]
    ds = (20, 17, 24)                      # all bucket to 32
    return [SpmmRequest(tenant=f"t{i}", a=a,
                        x=rng.standard_normal(
                            (a.shape[1], d)).astype(np.float32))
            for i, (a, d) in enumerate(zip(mats, ds))]


# -- d bucketing --------------------------------------------------------------

def test_d_bucket():
    assert d_bucket(1) == 8
    assert d_bucket(8) == 8
    assert d_bucket(9) == 16
    assert d_bucket(24) == 32
    assert d_bucket(64) == 64
    with pytest.raises(ValueError):
        d_bucket(0)


# -- batched == solo bit-identity --------------------------------------------

@pytest.mark.parametrize("backend", FUSED)
@pytest.mark.parametrize("staging", STAGINGS)
def test_batched_bit_identical_to_solo(backend, staging):
    """The acceptance invariant: a request served in a batch produces
    the SAME BITS as the same request served alone with the same knobs
    (slot padding, d-bucketing, and the common CGCM width must not
    perturb per-lane accumulation order)."""
    reqs = _tenants()
    kw = dict(**FUSED[backend], staging=staging, interpret=True,
              max_batch=8, cache=JitCache())
    server = SpmmServer(**kw)
    solo = [server.serve([r])[0] for r in reqs]
    batched = server.serve(reqs)
    assert all(r.batch_size == len(reqs) for r in batched)
    for s, b in zip(solo, batched):
        assert s.y.shape == b.y.shape
        assert np.array_equal(s.y, b.y), \
            f"{b.tenant}: batched bits diverge from solo"


def test_batched_matches_ref_numerics():
    reqs = _tenants()
    server = SpmmServer(interpret=True, cache=JitCache())
    for resp, req in zip(server.serve(reqs), reqs):
        ref = spmm(req.a, jnp.asarray(req.x), backend="ref")
        np.testing.assert_allclose(resp.y, np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("backend", FUSED)
def test_batched_is_one_fused_dispatch(backend):
    """R stacked requests cost ONE pallas_call, not R (counted at trace
    time like the sharded twin in test_sharded_fused)."""
    reqs = _tenants()
    compiled = compile_batched_spmm(
        [r.a for r in reqs], 32, **FUSED[backend], interpret=True,
        cache=JitCache())
    counter = "bcsr_fused"
    ops.reset_dispatch_counts()
    ys = compiled([r.a.vals for r in reqs], [r.x for r in reqs])
    assert ops.DISPATCH_COUNTS[counter] == 1
    assert ops.DISPATCH_COUNTS[counter + "_sharded"] == 0
    assert len(ys) == len(reqs)
    # warm re-dispatch reuses the traced executable: no new trace
    compiled([r.a.vals for r in reqs], [r.x for r in reqs])
    assert ops.DISPATCH_COUNTS[counter] == 1


def test_batched_workspace_uniform_windows():
    """The flattened dispatch has ONE static DMA window, so every
    block's window must stay inside its own request's stream region
    (request-axis stacking uses uniform windows, unlike the chip axis
    which keeps per-member ones)."""
    reqs = _tenants()
    compiled = CompiledBatchedSpmm([r.a for r in reqs], 32,
                                   backend="pallas_bcsr", mxu_gain=0.0,
                                   interpret=True)
    bw = compiled.batched_workspace
    R = bw.n_requests
    B = bw.num_blocks // R
    S = bw.gather_flat.size // R
    Sc = bw.cols_flat.size // R
    for q in range(bw.num_blocks):
        r = q // B
        assert bw.blk_off[q] >= r * S
        assert bw.blk_off[q] + bw.max_span <= (r + 1) * S
        assert bw.blk_coff[q] >= r * Sc
        assert bw.blk_coff[q] + bw.max_cspan <= (r + 1) * Sc
    total_nnz = sum(int(r.a.vals.size) for r in reqs)
    assert bw.gather_flat.min() >= 0
    assert bw.gather_flat.max() <= total_nnz    # == total -> zero slot


def test_mixed_buckets_split_into_separate_dispatches():
    rng = np.random.default_rng(3)
    a = random_csr(32, 32, density=0.1, seed=5)
    r16 = SpmmRequest("small", a, rng.standard_normal(
        (32, 12)).astype(np.float32))
    r64 = SpmmRequest("wide", a, rng.standard_normal(
        (32, 40)).astype(np.float32))
    server = SpmmServer(interpret=True, cache=JitCache())
    out = server.serve([r16, r64, r16, r64])
    assert [o.tenant for o in out] == ["small", "wide", "small", "wide"]
    # two buckets -> two fused dispatches, each batching its pair
    assert server.batches_dispatched == 2
    assert all(o.batch_size == 2 for o in out)
    assert out[0].y.shape == (32, 12) and out[1].y.shape == (32, 40)
    np.testing.assert_array_equal(out[0].y, out[2].y)


# -- cross-request cache behavior --------------------------------------------

def test_second_request_is_pure_cache_hit():
    """Acceptance: the second request for a cached shape performs zero
    plan/pack work — asserted on BUILD_SECONDS and JitCache.stats()."""
    reqs = _tenants()
    server = SpmmServer(interpret=True, cache=JitCache())
    first = server.serve(reqs)
    assert not any(r.cache_hit for r in first)
    hits0 = server.cache.stats()["hits"]
    ops.reset_dispatch_counts()            # clears BUILD_SECONDS too
    second = server.serve(reqs)
    assert all(r.cache_hit for r in second)
    assert ops.BUILD_SECONDS["plan"] == 0.0
    assert ops.BUILD_SECONDS["pack"] == 0.0
    assert server.cache.stats()["hits"] > hits0
    assert server.cache.stats()["misses"] == \
        server.cache.stats()["entries"]
    for a, b in zip(first, second):
        assert np.array_equal(a.y, b.y)


def test_concurrent_first_requests_single_flight():
    """N threads racing the same cold structure pay exactly ONE build."""
    a = random_csr(48, 48, density=0.08, seed=9)
    server = SpmmServer(interpret=True, cache=JitCache())
    barrier = threading.Barrier(6)
    errs = []

    def hit():
        try:
            barrier.wait()
            server.warmup(a, 24)
        except BaseException as e:          # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=hit) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    st = server.cache.stats()
    assert st["misses"] == 1
    assert st["entries"] == 1
    assert st["hits"] == 5


def test_clear_does_not_resurrect_inflight_build():
    """Regression: clear() racing an in-flight build used to leave the
    pre-clear builder free to re-insert its stale artifact (and a stale
    event in _inflight).  The builder's own caller still gets its
    value; the cache must not."""
    cache = JitCache()
    started, release = threading.Event(), threading.Event()
    got = []

    def slow_builder():
        started.set()
        assert release.wait(10)
        return "stale"

    t = threading.Thread(
        target=lambda: got.append(cache.get_or_build(("k",),
                                                     slow_builder)))
    t.start()
    assert started.wait(10)
    cache.clear()                 # invalidates the in-flight build
    release.set()
    t.join(10)
    assert got == ["stale"]       # pre-clear caller keeps its result
    # post-clear state: no resurrected entry, no stale inflight event
    assert cache.stats()["entries"] == 0
    assert cache._inflight == {}
    assert cache.get_or_build(("k",), lambda: "fresh") == "fresh"


def test_clear_while_waiters_blocked_recovers():
    """Waiters parked on a pre-clear build must re-loop onto the new
    inflight map and converge (no deadlock, no stale value)."""
    cache = JitCache()
    started, release = threading.Event(), threading.Event()

    def slow_builder():
        started.set()
        assert release.wait(10)
        return "old"

    results = []
    builder_t = threading.Thread(
        target=lambda: results.append(("b",
                                       cache.get_or_build(("k",),
                                                          slow_builder))))
    builder_t.start()
    assert started.wait(10)
    waiter_t = threading.Thread(
        target=lambda: results.append(("w",
                                       cache.get_or_build(("k",),
                                                          lambda: "new"))))
    waiter_t.start()
    cache.clear()
    release.set()
    builder_t.join(10)
    waiter_t.join(10)
    assert dict(results)["b"] == "old"
    assert dict(results)["w"] == "new"      # not the invalidated build
    assert cache.get_or_build(("k",), lambda: "newest") == "new"


def test_server_stats_shape():
    server = SpmmServer(interpret=True, cache=JitCache())
    server.serve(_tenants()[:2])
    s = server.stats()
    assert s["tenants"] == 2
    assert s["requests_served"] == 2
    assert s["batches_dispatched"] == 1
    for k in ("entries", "hits", "misses", "evictions"):
        assert k in s


def test_server_rejects_non_fused_backend():
    with pytest.raises(ValueError, match="fused"):
        SpmmServer(backend="ref", interpret=True, cache=JitCache())


# -- generate-driver regressions ---------------------------------------------

def _tiny_model():
    cfg = reduced(get_config("rwkv6-1.6b"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jnp.asarray(np.random.default_rng(0).integers(
        2, cfg.vocab_size, size=(2, 8)), jnp.int32)
    return cfg, model, params, prompts


def test_generate_sampling_without_rng():
    """Regression: greedy=False with rng=None used to crash in
    jax.random.split(None)."""
    cfg, model, params, prompts = _tiny_model()
    out = generate(model, params, prompts, gen_len=4, cache_len=16,
                   greedy=False, rng=None)
    assert out.shape == (2, 12)
    toks = np.asarray(out)
    assert toks.min() >= 0 and toks.max() < cfg.vocab_size


def test_generate_sampling_deterministic_per_key():
    _, model, params, prompts = _tiny_model()
    a = generate(model, params, prompts, gen_len=4, cache_len=16,
                 greedy=False, rng=jax.random.PRNGKey(7))
    b = generate(model, params, prompts, gen_len=4, cache_len=16,
                 greedy=False, rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_generate_does_not_retrace_per_call():
    """Regression: generate used to rebuild jax.jit(lambda ...) each
    call, retracing prefill per request.  Trace count is observed by
    shimming prefill — the jitted callable only runs the python body at
    trace time."""
    _, model, params, prompts = _tiny_model()
    traces = {"prefill": 0}
    orig = model.prefill

    def counting_prefill(*a, **kw):
        traces["prefill"] += 1
        return orig(*a, **kw)

    model.prefill = counting_prefill
    for _ in range(3):
        generate(model, params, prompts, gen_len=3, cache_len=16)
    assert traces["prefill"] == 1
    # a different cache_len is a different specialization: one more
    generate(model, params, prompts, gen_len=3, cache_len=24)
    assert traces["prefill"] == 2


def test_serve_callables_memoized_per_model():
    _, model, _, _ = _tiny_model()
    p1, d1 = _serve_callables(model, 16)
    p2, d2 = _serve_callables(model, 16)
    assert p1 is p2 and d1 is d2
    p3, _ = _serve_callables(model, 32)
    assert p3 is not p1
    _, model2, _, _ = _tiny_model()
    q1, _ = _serve_callables(model2, 16)
    assert q1 is not p1
