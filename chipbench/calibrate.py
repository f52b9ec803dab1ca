#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, in one process.

    python3 chipbench/calibrate.py --workload pokec.spmm_fwd \\
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control 3 --out FILE

For each seed: new operands, one step of the timed path (the artifact
is built once and reused, as the window reuses it), and each output's
widest and root-mean-square scaled gap to the reference: the lower
readings.  For the first ``--control`` seeds also the control (the
reference one precision step below the configuration's) against the
reference: the upper readings.
One JSON line per seed goes to ``--out`` and to standard output.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import run as R  # noqa: E402


def gaps(outputs: dict, ref: dict) -> dict:
    return {name: {stat: R.scaled_error(outputs[name], ref[name], stat)
                   for stat in ("max", "rms")} for name in ref}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return R.NO_CHIP
    bench = json.loads((R.ROOT / "BENCHMARK.json").read_text())
    cell = R.resolve_cell(bench, R.ROOT, args.workload)
    sys.path.insert(0, str(R.ROOT / "src"))
    kind = cell.traffic["kind"]
    steps_mod = R.load_module("steps", kind)
    ref_mod = R.load_module("reference", kind)
    structure = R.structure_of(cell, R.CACHE_DIR)
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        step = steps_mod.build(structure, cell.config, cell.traffic,
                               R.seed_key(seed))
        outputs = jax.block_until_ready(step.call())
        ref = ref_mod.compute(structure, cell.config, cell.traffic,
                              step.inputs, "reference")
        row = {"workload": args.workload, "seed": seed,
               "program": gaps(outputs, ref)}
        del outputs
        if i < args.control:
            ctrl = ref_mod.compute(structure, cell.config, cell.traffic,
                                   step.inputs, "control")
            row["control"] = gaps(ctrl, ref)
            del ctrl
        row["seconds"] = time.perf_counter() - t0
        del ref, step
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
