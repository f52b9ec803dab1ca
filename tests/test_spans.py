"""The program's profiler spans (``repro.kernels.ops.span``): each eager
call opens one ``.call`` span, with its stage spans nested inside in
call-path order, as a CPU profiler trace records them."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import (compile_batched_spmm, compile_sparse_attention,
                        compile_spmm, random_csr)
from repro.core.jit_cache import JitCache
from repro.kernels import ops
from repro.models.sparse_attention import sparse_attention_mask

PREFIXES = ("spmm.", "attn.", "spmm_batched.")


def _stages(family):
    return [(f"{family}.{s}", [])
            for s in ("stage_vals", "stage_operands", "kernel",
                      "unpermute")]


def _tree(events):
    """Nest ``(start, end, name)`` intervals: ``[(name, children)]``."""
    root, stack = [], []
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        node = (name, [])
        (stack[-1][1] if stack else root).append(node)
        stack.append((end, node[1]))
    return root


def traced_spans(fn, trace_dir, calls=2):
    """The program spans of ``calls`` eager calls of ``fn`` (warmed
    first, outside the trace), nested."""
    jax.block_until_ready(fn())
    jax.profiler.start_trace(str(trace_dir))
    try:
        for _ in range(calls):
            jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith(PREFIXES)]
    return _tree(events)


def _spmm_case(backend, grad=False, **kw):
    a = random_csr(48, 40, density=0.15, family="powerlaw", seed=3)
    c = compile_spmm(a, 16, backend=backend, interpret=True,
                     cache=JitCache(), **kw)
    vals = jnp.asarray(a.vals)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((40, 16)),
                    jnp.float32)
    if not grad:
        return lambda: c(vals, x)
    dy = jnp.ones((48, 16), jnp.float32)

    def vjp():
        y, pull = jax.vjp(c, vals, x)
        return y, pull(dy)
    return vjp


def _attn_case():
    a = sparse_attention_mask(32, 8, 2)
    c = compile_sparse_attention(a, 16, backend="pallas_bcsr",
                                 interpret=True, cache=JitCache())
    rng = np.random.default_rng(6)
    q, k, v = (jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
               for _ in range(3))
    vals = jnp.asarray(a.vals)
    return lambda: c(vals, q, k, v)


def _batched_case():
    mats = [random_csr(24, 24, density=0.2, seed=s) for s in (7, 8)]
    c = compile_batched_spmm(mats, 8, interpret=True, cache=JitCache())
    vals = [jnp.asarray(a.vals) for a in mats]
    xs = [np.ones((24, 8), np.float32)] * 2
    return lambda: c(vals, xs)


CASES = {
    "spmm_bcsr": (lambda: _spmm_case("pallas_bcsr"),
                  [("spmm.call", _stages("spmm"))]),
    "spmm_ell_sharded": (lambda: _spmm_case("pallas_bcsr", n_chips=1,
                                            mxu_gain=0.0),
                         [("spmm.call", _stages("spmm"))]),
    "spmm_ref": (lambda: _spmm_case("ref"), [("spmm.call", [])]),
    "spmm_vjp": (lambda: _spmm_case("pallas_bcsr", grad=True),
                 [("spmm.call", _stages("spmm")), ("spmm.sddmm", []),
                  ("spmm.transpose", _stages("spmm"))]),
    "attn": (_attn_case, [("attn.call", _stages("attn"))]),
    "spmm_batched": (_batched_case, [("spmm_batched.call", [])]),
}


@pytest.mark.parametrize("case", CASES)
def test_each_call_opens_its_span_and_stages_in_order(tmp_path, case):
    make, per_call = CASES[case]
    assert traced_spans(make(), tmp_path) == per_call * 2


def test_spans_leave_results_and_dispatches_unchanged(tmp_path):
    fn = _spmm_case("pallas_bcsr")
    want = np.asarray(fn())
    ops.reset_dispatch_counts()
    traced_spans(fn, tmp_path, calls=1)
    assert ops.DISPATCH_COUNTS["bcsr_fused"] == 2    # warm call + traced
    np.testing.assert_array_equal(np.asarray(fn()), want)


def test_span_is_a_trace_annotation():
    with ops.span("spmm.call") as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)
