#!/usr/bin/env python3
"""Run one cell traced, as ``run.py --trace 1`` does, and print its
result line with the window's device time split by program span.

    python3 chipbench/span_split.py --workload pokec.spmm_fwd --seed 7 \\
        --seconds 40

``run.py``'s trace reduction does not fill a span split, and it reads
only the metrics ``BENCHMARK.json`` lists.  This script adds, for one
run, ``span_reduce.charge`` of the same profile as ``Reduced.spans``
and reports under ``split``:

    vals_gather_ms, operand_prep_ms, unpermute_ms, launches_per_step
                   the readers in ``metrics/`` of that split
    kernel_span_ms device time per step under ``*.kernel`` spans
    charged_share  share of device busy time charged to a program span

and ``spans``, the split itself (seconds and launches over the window).
"""
from __future__ import annotations

import argparse
import json
import sys

if __package__:
    from . import run as R
else:
    import run as R

READERS = ("vals_gather_ms", "operand_prep_ms", "unpermute_ms",
           "launches_per_step")


def run_split(workload: str, seed: int, seconds: float, **run_kwargs):
    """``run.run`` traced, with ``split`` and ``spans`` added to its
    result; None where the cell's chip is missing."""
    tr = R.load_module(".", "trace_reduce")
    sr = R.load_module(".", "span_reduce")
    reduce, reduced = tr.reduce, []

    def reduce_with_spans(profile, *args, **kwargs):
        r = reduce(profile, *args, **kwargs)
        r.spans = sr.charge(profile)
        reduced.append(r)
        return r

    tr.reduce = reduce_with_spans
    try:
        result = R.run(workload, seed, seconds, True, **run_kwargs)
    finally:
        tr.reduce = reduce
    if result is None:
        return None
    (r,) = reduced
    readings = R.Readings(steps=result["attempted"], window_s=r.window_s,
                          setup_s=0.0, peak_bytes=0, build_seconds={},
                          least_s=0.0, trace=r)
    split = {name: R.load_module("metrics", name).read(readings)
             for name in READERS}
    split["kernel_span_ms"] = 1e3 * sum(
        v["device_s"] for name, v in r.spans.items()
        if name.endswith(".kernel")) / readings.steps
    split["charged_share"] = (
        1.0 - r.spans.get(sr.NONE, {"device_s": 0.0})["device_s"]
        / r.busy_s if r.busy_s > 0 else None)
    result["split"] = split
    result["spans"] = r.spans
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    result = run_split(args.workload, args.seed, args.seconds)
    if result is None:
        return R.NO_CHIP
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
