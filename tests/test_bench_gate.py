"""The bench-smoke regression gate (benchmarks/common.py) — the logic
CI's bench-smoke job trusts to fail on dispatch/wall regressions.

Pure record-level tests (no kernels, no timing): the acceptance
criterion "the gate demonstrably fails when fed a doctored baseline"
is asserted here so tier-1 proves it on every run, not just when a
human doctors a file by hand.
"""
import json

import pytest

from benchmarks.common import (CALIB_BENCH, bench_record,
                               check_bench_regression, format_bench_diff,
                               load_bench_json, write_bench_json)


def _rec(bench="fused_ell", strategy="nnz_split", backend="pallas_bcsr",
         n_chips=0, wall_ms=1.0, dispatches=1.0):
    return bench_record(bench, strategy, backend, n_chips, wall_ms,
                        dispatches)


def test_gate_passes_on_identical_records():
    recs = [_rec(), _rec(bench="codegen_plan", dispatches=0)]
    assert check_bench_regression(recs, recs) == []


def test_gate_passes_within_factor():
    base = [_rec(wall_ms=1.0)]
    pr = [_rec(wall_ms=1.9)]
    assert check_bench_regression(pr, base, factor=2.0) == []


def test_gate_fails_on_doctored_baseline_wall():
    """The ISSUE's doctored-baseline check: shrink the baseline wall
    10x and the same measurement must now trip the 2x gate."""
    pr = [_rec(wall_ms=10.0)]
    doctored = [_rec(wall_ms=1.0)]
    failures = check_bench_regression(pr, doctored, factor=2.0)
    assert len(failures) == 1 and "wall" in failures[0]


def test_sub_ms_cells_exempt_from_wall_gate_not_dispatch_gate():
    """Sub-ms baselines swing several-x on scheduler noise alone, so
    they gate on dispatches only (min_wall_ms floor)."""
    base = [_rec(wall_ms=0.4, dispatches=1)]
    noisy = [_rec(wall_ms=1.9, dispatches=1)]       # 4.75x wall "jump"
    assert check_bench_regression(noisy, base, factor=2.0) == []
    fused_broke = [_rec(wall_ms=0.4, dispatches=9)]
    assert check_bench_regression(fused_broke, base, factor=2.0)
    # an explicit lower floor re-enables the wall gate
    assert check_bench_regression(noisy, base, factor=2.0,
                                  min_wall_ms=0.1)


def test_gate_fails_on_dispatch_regression():
    """A fusion regression (one dispatch becoming many) must fail even
    when wall-clock happens to look fine."""
    base = [_rec(dispatches=1, wall_ms=1.0)]
    pr = [_rec(dispatches=8, wall_ms=1.0)]
    failures = check_bench_regression(pr, base, factor=2.0)
    assert len(failures) == 1 and "dispatch" in failures[0]


def test_gate_fails_on_missing_cell():
    base = [_rec(), _rec(strategy="row_split")]
    pr = [_rec()]
    failures = check_bench_regression(pr, base)
    assert len(failures) == 1 and "coverage" in failures[0]


def test_gate_ignores_new_pr_cells():
    base = [_rec()]
    pr = [_rec(), _rec(bench="fused_mixed", backend="pallas_bcsr")]
    assert check_bench_regression(pr, base) == []


def test_calib_scales_wall_threshold_up_only():
    """A 3x-slower runner (calib 1ms -> 3ms) relaxes the wall gate so a
    uniformly-3x-slower measurement still passes; a FASTER runner must
    NOT tighten the gate below the raw factor."""
    base = [bench_record(CALIB_BENCH, "-", "dense", 0, 1.0, 0),
            _rec(wall_ms=1.0)]
    slow = [bench_record(CALIB_BENCH, "-", "dense", 0, 3.0, 0),
            _rec(wall_ms=3.0)]
    assert check_bench_regression(slow, base, factor=2.0) == []
    # same slowdown WITHOUT the calibration record: gate trips
    assert check_bench_regression(slow[1:], base[1:], factor=2.0)
    # faster calib (0.2x) must not shrink thresholds: 1.5x wall passes
    fast = [bench_record(CALIB_BENCH, "-", "dense", 0, 0.2, 0),
            _rec(wall_ms=1.5)]
    assert check_bench_regression(fast, base, factor=2.0) == []


def test_calib_does_not_mask_real_regression():
    """Scaling is capped by the calib ratio itself: a cell that
    regresses far beyond the machine slowdown still fails."""
    base = [bench_record(CALIB_BENCH, "-", "dense", 0, 1.0, 0),
            _rec(wall_ms=1.0)]
    pr = [bench_record(CALIB_BENCH, "-", "dense", 0, 1.5, 0),
          _rec(wall_ms=10.0)]
    failures = check_bench_regression(pr, base, factor=2.0)
    assert len(failures) == 1 and "wall" in failures[0]


def test_json_roundtrip_and_validation(tmp_path):
    recs = [_rec(), bench_record(CALIB_BENCH, "-", "dense", 0, 0.5, 0)]
    p = tmp_path / "bench.json"
    write_bench_json(p, recs)
    assert load_bench_json(p) == recs
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([{"bench": "x"}]))
    with pytest.raises(ValueError):
        load_bench_json(bad)
    notalist = tmp_path / "notalist.json"
    notalist.write_text(json.dumps({"bench": "x"}))
    with pytest.raises(ValueError):
        load_bench_json(notalist)


def test_diff_table_verdicts_match_the_gate():
    """The job-summary markdown table is rendered from the SAME gate
    call CI exits on: a regressed cell shows REGRESSION, a vanished
    cell shows the coverage failure, a new PR cell shows as new, and
    everything else is OK — one row per cell in the union."""
    base = [bench_record(CALIB_BENCH, "-", "dense", 0, 1.0, 0),
            _rec(wall_ms=1.0),                        # regresses
            _rec(bench="fused_mixed", wall_ms=1.0),   # stays fine
            _rec(bench="gone", wall_ms=5.0)]          # disappears
    pr = [bench_record(CALIB_BENCH, "-", "dense", 0, 1.0, 0),
          _rec(wall_ms=10.0),
          _rec(bench="fused_mixed", wall_ms=1.1),
          _rec(bench="brand_new", wall_ms=1.0)]
    table = format_bench_diff(pr, base, factor=2.0)
    rows = {line.split("|")[1].strip(): line
            for line in table.splitlines() if line.startswith("| `")}
    assert len(rows) == 5
    assert "REGRESSION" in rows["`fused_ell/nnz_split/pallas_bcsr/0`"]
    assert "coverage" in rows["`gone/nnz_split/pallas_bcsr/0`"]
    assert "new" in rows["`brand_new/nnz_split/pallas_bcsr/0`"]
    assert "OK" in rows["`fused_mixed/nnz_split/pallas_bcsr/0`"]
    assert "calib" in rows["`calib/-/dense/0`"]
    # the wall ratio column is machine-scale normalized: 10x shows 10.00
    assert "| 10.00 |" in rows["`fused_ell/nnz_split/pallas_bcsr/0`"]


def test_diff_table_scale_relaxes_ratio():
    """A 2x-slower runner halves the displayed ratio, mirroring the
    gate's calib normalization."""
    base = [bench_record(CALIB_BENCH, "-", "dense", 0, 1.0, 0),
            _rec(wall_ms=1.0)]
    pr = [bench_record(CALIB_BENCH, "-", "dense", 0, 2.0, 0),
          _rec(wall_ms=3.0)]
    table = format_bench_diff(pr, base, factor=2.0)
    assert "machine scale 2.00" in table
    row = next(line for line in table.splitlines()
               if line.startswith("| `fused_ell"))
    assert "| 1.50 |" in row and "OK" in row


def test_checked_in_baseline_is_valid():
    """The baseline CI gates on must stay schema-valid and cover the
    fused hot-path cells (staged + not, sharded + not, merged, tuned)."""
    from pathlib import Path
    baseline = load_bench_json(
        Path(__file__).resolve().parents[1] / "BENCH_baseline.json")
    benches = {r["bench"] for r in baseline}
    assert {"calib", "fused_mixed", "fused_mixed_sharded",
            "fused_mixed_dma", "fused_mixed_dma_sharded",
            "fused_mixed_xshard", "fused_mixed_dma_xshard",
            "fused_mixed_merged", "fused_mixed_tuned", "codegen_plan",
            "attn_fused", "attn_fused_dma"} <= benches
    backends = {r["backend"] for r in baseline}
    assert "pallas_bcsr" in backends
