"""Tests for the repo invariant linter (tools/lint_invariants.py,
DESIGN.md §15) and regression tests for the violations it flagged on
the pre-linter tree.

Each rule is exercised twice: on a synthetic snippet that violates it
(proving the rule can fire) and on the shipped tree (proving the tree
is clean — the same gate CI runs).  The top_k regression pins the one
real cache-key hole the linter caught: ``top_k`` decides which
predicted candidates get measured, hence the winner, so it must join
the tune key.
"""
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.lint_invariants import (Finding, lint_source,  # noqa: E402
                                   lint_tree, main)


def _rules(findings):
    return {f.rule for f in findings}


# -- rule 1: cache-key completeness ------------------------------------------


def test_cache_key_omitted_knob_is_flagged():
    findings = lint_source(
        "def compile_spmm(a, d, *, bm=8, staging='auto', cache=None):\n"
        "    key = ('spmm', a.fingerprint, d, bm)\n"
        "    return cache.get_or_build(key, lambda: None)\n")
    assert _rules(findings) == {"cache-key"}
    assert "staging" in findings[0].message


def test_cache_key_complete_key_is_clean():
    findings = lint_source(
        "def compile_spmm(a, d, *, bm=8, staging='auto', cache=None):\n"
        "    key = ('spmm', a.fingerprint, d, bm, staging)\n"
        "    return cache.get_or_build(key, lambda: None)\n")
    assert findings == []


def test_cache_key_allowlisted_plumbing_is_exempt():
    findings = lint_source(
        "def compile_spmm(a, d, *, bm=8, cache=None, cache_priority=0.0,\n"
        "                 autotune=False, top_k=3, n_chips=None):\n"
        "    key = ('spmm', a.fingerprint, d, bm)\n"
        "    return cache.get_or_build(key, lambda: None)\n")
    assert findings == []


def test_cache_key_delegating_wrapper_without_key_is_skipped():
    findings = lint_source(
        "def compile_spmm(a, d, *, bm=8):\n"
        "    return compile_spmm_impl(a, d, bm=bm)\n")
    assert findings == []


def test_autotune_key_omitted_knob_is_flagged():
    findings = lint_source(
        "def autotune_spmm_with_result(a, d, *, merge_threshold=0,\n"
        "                              cache=None):\n"
        "    key = spmm_tune_key(a, d)\n"
        "    return cache.get_or_build(key, lambda: None)\n")
    assert _rules(findings) == {"cache-key"}
    assert "merge_threshold" in findings[0].message


def test_autotune_key_passed_knob_is_clean():
    findings = lint_source(
        "def autotune_spmm_with_result(a, d, *, merge_threshold=0,\n"
        "                              validate=None, cache=None):\n"
        "    key = spmm_tune_key(a, d, merge_threshold=merge_threshold)\n"
        "    return cache.get_or_build(key, lambda: None)\n")
    assert findings == []


# -- rule 2: dispatch-count registry -----------------------------------------

_OPS = (
    "DISPATCH_KEYS = frozenset({'good', 'stale'})\n"
    "DISPATCH_COUNTS = {}\n"
    "def thing_op(x):\n"
    "    DISPATCH_COUNTS['good'] += 1\n")


def test_unregistered_dispatch_key_is_flagged():
    findings = lint_source(
        "def f():\n    DISPATCH_COUNTS['rogue'] += 1\n",
        ops_source=_OPS)
    assert any("rogue" in f.message for f in findings
               if f.rule == "dispatch-count")


def test_non_literal_dispatch_key_is_flagged():
    findings = lint_source(
        "def f(k):\n    DISPATCH_COUNTS[k] += 1\n", ops_source=_OPS)
    assert any("non-literal" in f.message for f in findings)


def test_stale_registry_entry_is_flagged():
    findings = lint_source("x = 1\n", ops_source=_OPS)
    assert any("stale" in f.message for f in findings)


def test_silent_op_entry_point_is_flagged():
    ops = _OPS + "def quiet_op(x):\n    return x\n"
    findings = lint_source(
        "def f():\n    DISPATCH_COUNTS['stale'] += 1\n", ops_source=ops)
    assert any("quiet_op" in f.message for f in findings)


def test_snippet_without_counters_skips_the_registry_rule():
    findings = lint_source("def f():\n    return 1\n")
    assert findings == []


# -- rule 3: lock discipline -------------------------------------------------

_CACHE_SNIPPET = (
    "import threading\n"
    "class JitCache:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._entries = {}\n"
    "        self.hits = 0\n"
    "    def bad(self, k):\n"
    "        self._entries.pop(k, None)\n"
    "        self.hits += 1\n"
    "    def good(self, k):\n"
    "        with self._lock:\n"
    "            self._entries.pop(k, None)\n"
    "            del self._entries[k]\n"
    "    def evict_locked(self, k):\n"
    "        self._entries.clear()\n")


def test_unlocked_mutation_is_flagged_lock_and_init_exempt():
    findings = [f for f in lint_source(_CACHE_SNIPPET)
                if f.rule == "lock-discipline"]
    assert len(findings) == 2           # both lines of bad(), only bad()
    assert all("bad()" in f.message for f in findings)


def test_class_without_lock_is_not_held_to_the_rule():
    findings = lint_source(
        "class Stats:\n"
        "    def __init__(self):\n"
        "        self.hits = 0\n"
        "    def bump(self):\n"
        "        self.hits += 1\n")
    assert findings == []


# -- the shipped tree is clean (the CI gate) ---------------------------------


def test_real_tree_is_clean():
    findings = lint_tree(REPO / "src")
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_exit_codes(tmp_path):
    assert main(["--root", str(REPO / "src")]) == 0
    bad = tmp_path / "mod.py"
    bad.write_text(
        "def compile_x(a, *, knob=1, cache=None):\n"
        "    key = ('x', a.fingerprint)\n"
        "    return cache.get_or_build(key, lambda: None)\n")
    assert main(["--root", str(tmp_path)]) == 1


def test_cli_runs_as_a_script():
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "lint_invariants.py")],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_registry_matches_runtime_counters():
    # the frozenset the linter parses is the same object the runtime
    # increments into — importing proves the literal stays evaluable
    from repro.kernels.ops import DISPATCH_KEYS
    assert DISPATCH_KEYS == {
        "bcsr_fused", "bcsr_fused_dma", "bcsr_fused_sharded",
        "bcsr_fused_xshard", "attn_fused", "attn_fused_dma",
        "attn_fused_sharded"}


def test_finding_str_is_clickable():
    f = Finding("cache-key", "src/x.py", 7, "boom")
    assert str(f) == "src/x.py:7: [cache-key] boom"


# -- top_k regression: the cache-key hole the linter caught ------------------


def test_top_k_joins_the_tune_key():
    from repro.core.autotune import spmm_tune_key
    from repro.core.csr import random_csr
    a = random_csr(16, 16, density=0.2, seed=0)
    k1 = spmm_tune_key(a, 4, backend="pallas_bcsr", interpret=True,
                       x_sharding="replicated", mesh=None,
                       candidates=[], top_k=1)
    k3 = spmm_tune_key(a, 4, backend="pallas_bcsr", interpret=True,
                       x_sharding="replicated", mesh=None,
                       candidates=[], top_k=3)
    assert k1 != k3


def test_top_k_changes_the_measured_winner_not_a_shared_memo():
    # BEFORE the fix the second search returned the first's memoized
    # TuneResult; now each top_k gets its own search.  The fake timer
    # inverts the predicted ranking, so widening the measured pool
    # MUST change the winner.
    from repro.core.autotune import (autotune_spmm_with_result,
                                     default_candidates)
    from repro.core.csr import random_csr
    from repro.core.jit_cache import JitCache

    a = random_csr(24, 24, density=0.2, seed=1)
    cands = default_candidates(staging="resident", mxu_gain=0.0)
    assert len(cands) >= 2
    cache = JitCache()

    calls = {"n": 0}

    def inverted_timer(compiled, vals, x):
        calls["n"] += 1
        return 1.0 / calls["n"]     # later finalists measure faster

    _, narrow = autotune_spmm_with_result(
        a, 4, backend="pallas_bcsr", interpret=True,
        candidates=cands, measure=inverted_timer, top_k=1,
        cache=cache)
    _, wide = autotune_spmm_with_result(
        a, 4, backend="pallas_bcsr", interpret=True,
        candidates=cands, measure=inverted_timer, top_k=len(cands),
        cache=cache)
    assert len(narrow.measured_s) == 1
    assert len(wide.measured_s) == len(cands)
    assert narrow.config != wide.config


def test_server_threads_top_k_into_its_tune_lookups():
    import inspect

    from repro.launch.serve import SpmmServer
    sig = inspect.signature(SpmmServer.__init__)
    assert "top_k" in sig.parameters
    np.testing.assert_equal(sig.parameters["top_k"].default, 3)
