"""The control of each cell's comparison: the reference computed one
precision step below the configuration's must fail the limit that a
sound run passes, here at small sizes on the CPU.  The limits were set
from chip readings at the cells' own sizes (PERF.md)."""
import json
import sys

import pytest

from chipbench import run as R
from chipbench.tests.cells import GRAPH, MASK

CASES = [("spmm_fwd", GRAPH, "spmm"), ("spmm_train", GRAPH, "spmm"),
         ("attn_fwd", MASK, "attention")]


@pytest.mark.parametrize("traffic_name,config,kind", CASES)
def test_control_fails_the_limit(traffic_name, config, kind):
    sys.path.insert(0, str(R.ROOT / "src"))
    traffic = json.loads((R.BENCH_DIR / "traffic" /
                          f"{traffic_name}.json").read_text())
    gen = R.load_module("gen", config["structure"]["generator"])
    structure = gen.generate(config["structure"],
                             config["structure"].get("seed", 0))
    steps = R.load_module("steps", kind)
    ref_mod = R.load_module("reference", kind)
    worst = {}
    for seed in (1, 2, 3):
        inputs = steps.build(structure, config, traffic,
                             R.seed_key(seed)).inputs
        ref = ref_mod.compute(structure, config, traffic, inputs,
                              "reference")
        ctrl = ref_mod.compute(structure, config, traffic, inputs,
                               "control")
        for name in traffic["limits"]:
            e = R.scaled_error(ctrl[name], ref[name])
            worst[name] = max(worst.get(name, 0.0), e)
    assert any(worst[n] > limit for n, limit in traffic["limits"].items())
