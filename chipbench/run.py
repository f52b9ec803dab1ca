#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON line.

    python3 chipbench/run.py --workload pokec.spmm_fwd --seed 7 \\
        --seconds 40 --trace 0

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
configuration and a traffic mix.  Everything about it is found by name:

    configs/<file>            the configuration (``file`` in BENCHMARK.json)
    gen/<generator>.py        its sparsity structure, from a fixed seed
    traffic/<traffic>.json    the traffic mix; ``kind`` names the rest
    steps/<kind>.py           builds the timed step through the program
    work/<kind>.py            the step's least work: flops and bytes
    reference/<kind>.py       the plain jnp reference and its control
    metrics/<metric>.py       one reader per metric in BENCHMARK.json
    peaks.json                the chip's peaks by ``device_kind``

A traffic file holds ``kind``, the parameters its step takes (``grad``)
and ``limits``: for each output compared, the limit of its widest scaled
gap to the reference.

Set-up (structure, operands, ``compile_*``, warm steps) runs first;
then the step runs back to back, each ending in ``block_until_ready``,
until ``--seconds`` have passed.  Afterwards the outputs of a step drawn
from the seed and of the last step are compared with the reference.
``--trace 1`` traces the window with the profiler and reports the
per-layer metrics instead of the end-to-end ones.  The last line of
standard output is the result; each number compared is printed with its
limit as the last lines of standard error.  Without a TPU (or with
fewer chips than the cell asks for) it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                      # noqa: E402
import dataclasses                                   # noqa: E402
import gc                                            # noqa: E402
import hashlib                                       # noqa: E402
import importlib.util                                # noqa: E402
import json                                          # noqa: E402
import sys                                           # noqa: E402
import tempfile                                      # noqa: E402
from pathlib import Path                             # noqa: E402

import jax                                          # noqa: E402
import jax.numpy as jnp                              # noqa: E402
import numpy as np                                   # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = BENCH_DIR / ".cache"
NO_CHIP = 3
WARMUP_STEPS = 1    # every program a step runs is compiled or loaded by then
SAMPLE_STEPS = 3    # the step compared beside the last is one of these


# backend compilations of this process, counted to show that none
# falls inside a measured window
COMPILES = []


def _count_compile(event: str, secs: float, **kwargs) -> None:
    if event.endswith("backend_compile_duration"):
        COMPILES.append(secs)


jax.monitoring.register_event_duration_secs_listener(_count_compile)


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_module(folder: str, name: str):
    """``<folder>/<name>.py`` under the benchmark, imported by path."""
    key = "chipbench_" + "".join(c if c.isalnum() else "_"
                                 for c in f"{folder}_{name}")
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, BENCH_DIR / folder / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    config_bytes: bytes
    traffic: dict
    end_to_end: list        # BENCHMARK.json metric entries of this cell
    per_layer: list


def _for_cell(metrics: list, workload: str) -> list:
    return [m for m in metrics
            if workload in m.get("workloads", [workload])]


def resolve_cell(bench: dict, root: Path, workload: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    raw = (root / cfg_entry["file"]).read_bytes()
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=workload, chips=int(w["chips"]),
                config=json.loads(raw), config_bytes=raw,
                traffic=traffic,
                end_to_end=_for_cell(bench["end_to_end"], workload),
                per_layer=_for_cell(bench["per_layer"], workload))


def structure_of(cell: Cell, cache_dir: Path):
    """The configuration's sparsity structure ``(row_ptr, cols,
    shape)``, generated once per checkout and kept as ``.npy`` keyed by
    the configuration file and its generator's source."""
    spec = cell.config["structure"]
    gen_path = BENCH_DIR / "gen" / f"{spec['generator']}.py"
    key = hashlib.sha256(cell.config_bytes + gen_path.read_bytes())
    base = cache_dir / "structure" / key.hexdigest()[:24]
    files = [base.with_suffix(f".{part}.npy")
             for part in ("row_ptr", "cols", "shape")]
    if all(f.exists() for f in files):
        row_ptr, cols, shape = (np.load(f) for f in files)
        return row_ptr, cols, tuple(int(s) for s in shape)
    row_ptr, cols, shape = load_module("gen", spec["generator"]).generate(
        spec, int(spec.get("seed", 0)))
    base.parent.mkdir(parents=True, exist_ok=True)
    for f, a in zip(files, (row_ptr, cols, np.asarray(shape))):
        tmp = f.with_suffix(".tmp.npy")
        np.save(tmp, a)
        tmp.replace(f)
    return row_ptr, cols, shape


def seed_key(seed: int):
    """A threefry key from any whole ``--seed``, however large."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def sampled_step(seed: int) -> int:
    """The step whose output is compared beside the last one."""
    return int(np.random.SeedSequence([int(seed), 1]).generate_state(1)[0]
               % SAMPLE_STEPS)


def peaks_for(device_kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["chips"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device_kind {device_kind!r} in "
                         f"chipbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


@dataclasses.dataclass
class Readings:
    """Everything a metric reader may read (see ``metrics/``)."""
    steps: int
    window_s: float
    setup_s: float
    peak_bytes: int
    build_seconds: dict     # the program's ops.BUILD_SECONDS after set-up
    least_s: float          # least time of one step's work at the peaks
    trace: object = None    # trace.Reduced of a traced window, or None


# -- faults planted by the tests: each must turn ``correct`` false -------

def _fault(outputs: dict, fault: str) -> dict:

    def alter(a):
        return a.reshape(-1).at[0].add(1.0).reshape(a.shape)

    def half(a):
        return a.at[a.shape[0] // 2:].set(0)

    names = list(outputs)
    out = dict(outputs)
    if fault == "alter":
        out[names[0]] = _map(alter, out[names[0]])
    elif fault == "half":
        out = {k: _map(half, v) for k, v in out.items()}
    elif fault == "stale":
        out = {k: (v if i == 0 else _map(jnp.zeros_like, v))
               for i, (k, v) in enumerate(out.items())}
    else:
        raise ValueError(fault)
    return out


def _map(fn, v):
    return [fn(a) for a in v] if isinstance(v, list) else fn(v)


# -- comparison ----------------------------------------------------------

def _gaps(o, r):
    """Each entry's gap over its row's largest reference magnitude,
    floored at the median row's (a vector is one value per row)."""
    o = o.reshape(o.shape[0], -1).astype(jnp.float32)
    r = r.reshape(r.shape[0], -1).astype(jnp.float32)
    row = jnp.max(jnp.abs(r), axis=1)
    scale = jnp.maximum(row, jnp.median(row))
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.abs(o - r) / scale[:, None], jnp.all(jnp.isfinite(o))


def scaled_error(out, ref, stat: str = "max") -> float:
    """The widest scaled gap between ``out`` and ``ref`` (``stat="rms"``:
    their root mean square).  Non-finite output reads infinite."""

    @jax.jit
    def err(o, r):
        gap, finite = _gaps(o, r)
        e = (jnp.max(gap) if stat == "max"
             else jnp.sqrt(jnp.mean(jnp.square(gap))))
        return jnp.where(finite, e, jnp.inf)

    if isinstance(out, list):
        out = jnp.stack(out)
    return float(err(out, ref))


def compare(outputs: list, ref: dict, limits: dict) -> dict:
    """``{name: {"value": the widest scaled gap of output ``name`` over
    the outputs compared, "limit": ...}}``"""
    return {name: {"value": max(scaled_error(o[name], ref[name])
                                for o in outputs),
                   "limit": float(limit)}
            for name, limit in limits.items()}


# -- one run -------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        bench: dict = None, root: Path = ROOT, cache_dir: Path = CACHE_DIR,
        require_chip: bool = True, device_kind: str = None,
        fault: str = None, t_start: float = None):
    """One run of one cell; returns the result object, or None (after a
    message) where the chip the cell asks for is missing.  The tests
    drive it with ``require_chip=False`` on small configurations."""
    t_start = T_START if t_start is None else t_start
    bench = (json.loads((root / "BENCHMARK.json").read_text())
             if bench is None else bench)
    cell = resolve_cell(bench, root, workload)

    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        log(f"{workload} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
        return None
    if devices[0].platform == "tpu":
        # a fixed directory of the checkout: the path is part of the key
        jax.config.update("jax_compilation_cache_dir",
                          str(cache_dir / "jax"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import ops

    kind = cell.traffic["kind"]
    steps_mod = load_module("steps", kind)
    dev = devices[0]
    peaks = peaks_for(device_kind or dev.device_kind)
    annotate = jax.profiler.TraceAnnotation

    marks = [("start", t_start), ("devices", time.perf_counter())]
    with annotate("generate"):
        structure = structure_of(cell, cache_dir)
    marks.append(("structure", time.perf_counter()))
    work = load_module("work", kind).count(structure, cell.config,
                                           cell.traffic)
    least_s = max(work["bytes"] / peaks["hbm_bytes_per_s"],
                  work["flops"] / peaks["flops_per_s"])
    ops.reset_dispatch_counts()
    with annotate("plan"):
        step = steps_mod.build(structure, cell.config, cell.traffic,
                               seed_key(seed))
    marks.append(("build", time.perf_counter()))
    with annotate("warmup"):
        for _ in range(WARMUP_STEPS):
            jax.block_until_ready(step.call())
    marks.append(("warmup", time.perf_counter()))
    log("set-up: " + ", ".join(f"{b[0]} {b[1] - a[1]:.3f} s"
                               for a, b in zip(marks, marks[1:])))
    stats = dev.memory_stats() or {}
    log(f"after set-up: {stats.get('bytes_in_use', 0)} bytes in use, "
        f"peak {stats.get('peak_bytes_in_use', 0)} bytes")
    build_seconds = dict(ops.BUILD_SECONDS)
    call = step.call if fault is None else (
        lambda: _fault(step.call(), fault))

    compiles_before = len(COMPILES)
    keep_at = sampled_step(seed)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    kept = out = None
    ends = []           # each step's end, seconds into the window
    gc.collect()
    gc.disable()
    with annotate("window"):
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        while True:
            # The window holds one older output while the next is made,
            # the sample compared later, as a caller's ``y = f(x)`` loop
            # holds its last result: the peak counts nothing else kept.
            out = None
            with annotate("step"):
                out = jax.block_until_ready(call())
            if len(ends) == keep_at:
                kept = out
            ends.append(time.perf_counter() - t0)
            if ends[-1] >= seconds and len(ends) > keep_at:
                break
    gc.enable()
    if trace:
        jax.profiler.stop_trace()
    steps, window_s = len(ends), ends[-1]
    in_window = len(COMPILES) - compiles_before
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    log(f"{workload} seed {seed}: {steps} steps in {window_s:.3f} s, "
        f"set-up {setup_s:.3f} s, {in_window} compiles in the window, "
        f"peak {peak} bytes")
    step_s = np.diff(ends, prepend=0.0)
    log(f"step seconds: median {np.median(step_s):.4f}, min "
        f"{step_s.min():.4f}, max {step_s.max():.4f} (step "
        f"{int(step_s.argmax())})")

    outputs = [kept] if kept is out else [kept, out]
    inputs = step.inputs
    del step, call, out, kept
    from repro.core import GLOBAL_CACHE
    GLOBAL_CACHE.clear()
    gc.collect()
    t_ref = time.perf_counter()
    ref = load_module("reference", kind).compute(
        structure, cell.config, cell.traffic, inputs, "reference")
    checks = compare(outputs, ref, cell.traffic["limits"])
    log(f"reference and comparison {time.perf_counter() - t_ref:.3f} s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    reduced = None
    if trace:
        reduced = load_module(".", "trace_reduce").reduce_dir(trace_dir)
    readings = Readings(steps=steps, window_s=window_s, setup_s=setup_s,
                        peak_bytes=peak, build_seconds=build_seconds,
                        least_s=least_s, trace=reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module("metrics", m["name"]).read(readings)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": steps, "failed": 0,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = {"device_ops": reduced.device_ops,
                               "idle_gaps": reduced.idle_gaps}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    if result is None:
        return NO_CHIP
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
