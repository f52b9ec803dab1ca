"""BENCHMARK.json names only pieces the harness can find by name."""
import json
import re

import pytest

from chipbench import run as R

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((R.ROOT / "BENCHMARK.json").read_text())
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_resolves(w):
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    cell = R.resolve_cell(BENCH, R.ROOT, w["name"])
    kind = cell.traffic["kind"]
    for folder in ("steps", "work", "reference"):
        assert (R.BENCH_DIR / folder / f"{kind}.py").exists()
    gen = cell.config["structure"]["generator"]
    assert (R.BENCH_DIR / "gen" / f"{gen}.py").exists()
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_each_metric_has_a_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert (R.BENCH_DIR / "metrics" / f"{m['name']}.py").exists()
    if m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    else:
        assert 0 < m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")


def test_config_files_are_under_the_benchmark():
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        cfg = json.loads((R.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
