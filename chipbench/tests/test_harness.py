"""A whole run of each cell on the CPU at small sizes, with the chip
check skipped: the result line, the no-chip exit, and the faults that
must turn ``correct`` false."""
import json

import pytest

from chipbench import run as R
from chipbench.tests.cells import WORKLOADS, run_small

FAULTS = {"pokec.spmm_fwd": ("alter", "half"),
          "longformer.attn_fwd": ("alter", "half"),
          "pokec.spmm_train": ("alter", "half", "stale")}


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(tmp_path, workload):
    res = run_small(tmp_path, workload)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"step_ms", "setup_s"}  # no HBM on a CPU
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    json.dumps(res)


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w, faults in FAULTS.items() for f in faults])
def test_planted_fault_is_not_correct(tmp_path, workload, fault):
    res = run_small(tmp_path, workload, seed=11, fault=fault)
    assert res["correct"] is False


def test_structure_is_cached_per_checkout(tmp_path):
    run_small(tmp_path, "pokec.spmm_fwd")
    cached = sorted((tmp_path / "cache" / "structure").iterdir())
    assert len(cached) == 3
    run_small(tmp_path, "pokec.spmm_train")      # same configuration
    assert sorted((tmp_path / "cache" / "structure").iterdir()) == cached


def test_without_a_chip_it_exits_and_prints_no_result(capsys):
    rc = R.main(["--workload", "pokec.spmm_fwd", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert rc == R.NO_CHIP
    assert capsys.readouterr().out == ""


def test_same_seed_same_operands():
    import numpy as np
    a, b, c = (R.seed_key(s) for s in (2**33 + 1, 2**33 + 1, 2**33 + 2))
    import jax
    ka, kb, kc = (np.asarray(jax.random.key_data(k)) for k in (a, b, c))
    assert np.array_equal(ka, kb) and not np.array_equal(ka, kc)
