"""Charging device programs to the program spans that launched them
(``span_reduce``), and the readers of that split."""
import dataclasses
import gzip

import pytest

from chipbench import run as R

sr = R.load_module(".", "span_reduce")
tr = R.load_module(".", "trace_reduce")

READERS = ("vals_gather_ms", "operand_prep_ms", "unpermute_ms",
           "launches_per_step")


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float
    stats: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Profile:
    planes: list


def ev(name, a, b, **stats):
    return Ev(name, a, b - a, stats)


def fake_profile():
    """One call in a 1,000 ns window, three launches on two chips.

    Launch 1 (python 20) is enqueued on the Python thread's runtime
    line inside ``spmm.stage_vals``; launch 2 (python 150) inside
    ``spmm.kernel`` is enqueued later on ``pjrt-tpu-tasks``; launch 3
    (python 600) comes after the call, under no program span.  A fourth
    module carries no flow at all."""
    python = Line("python3", [
        ev("window", 0, 1000), ev("step", 0, 1000),
        ev("spmm.call", 5, 400), ev("spmm.stage_vals", 10, 100),
        ev("spmm.kernel", 100, 300),
        ev("PJRT_LoadedExecutable_Execute linkage", 20, 21, _p=1001),
        ev("PJRT_LoadedExecutable_Execute linkage", 150, 151, _p=1002),
        ev("PJRT_LoadedExecutable_Execute linkage", 600, 601, _p=1003)])
    main = Line("main/1", [
        ev("PJRT_LoadedExecutable_Execute", 21, 40, _c=1001),
        ev("tpu::System::Execute", 25, 35, _p=2001),
        ev("tpu::System::Execute=>IssueSequencedEvent", 26, 34, _c=2001),
        ev("DoEnqueueProgram", 27, 29, run_id=1, _p=3001),
        ev("PJRT_LoadedExecutable_Execute", 151, 170, _c=1002),
        ev("tpu::System::Execute", 155, 160, _p=2002),
        ev("PJRT_LoadedExecutable_Execute", 601, 620, _c=1003),
        ev("tpu::System::Execute", 605, 610, _p=2003)])
    tasks = Line("pjrt-tpu-tasks/2", [
        ev("tpu::System::Execute=>IssueSequencedEvent", 200, 260, _c=2002),
        ev("DoEnqueueProgram", 210, 250, run_id=2, _p=3002),
        ev("tpu::System::Execute=>IssueSequencedEvent", 700, 760, _c=2003),
        ev("DoEnqueueProgram", 710, 750, run_id=3, _p=3003)])
    chip0 = Plane("/device:TPU:0", [Line("XLA Modules", [
        ev("jit_gather(1)", 50, 100, run_id=1, _c=3001),
        ev("jit_kernel(2)", 300, 600, run_id=2, _c=3002),
        ev("jit_gather(3)", 800, 900, run_id=3, _c=3003),
        ev("jit_other(4)", 950, 1100)])])
    chip1 = Plane("/device:TPU:1", [Line("XLA Modules", [
        ev("jit_gather(1)", 50, 150, run_id=1, _c=3001),
        ev("jit_kernel(2)", 300, 500, run_id=2, _c=3002)])])
    host = Plane("/host:CPU", [python, main, tasks])
    return Profile([Plane("/host:metadata", []), chip0, chip1, host])


def test_modules_are_charged_through_their_launch_chain():
    spans = sr.charge(fake_profile())
    # chip 0: 50 and 300 ns; chip 1: 100 and 200 ns; averaged over both
    assert spans["spmm.stage_vals"] == {"device_s": pytest.approx(75e-9),
                                        "launches": 1.0}
    assert spans["spmm.kernel"] == {"device_s": pytest.approx(250e-9),
                                    "launches": 1.0}
    # launch 3 (no span) and the flowless module, clipped to the window
    assert spans[sr.NONE] == {"device_s": pytest.approx(75e-9),
                              "launches": 1.0}
    assert list(spans) == ["spmm.kernel", "spmm.stage_vals", sr.NONE]


def test_split_sums_to_busy_time():
    profile = fake_profile()
    busy = tr.reduce(profile).busy_s
    assert sum(v["device_s"] for v in sr.charge(profile).values()) == (
        pytest.approx(busy))


@pytest.mark.parametrize("windows", [0, 2])
def test_window_must_be_traced_once(windows):
    p = fake_profile()
    line = p.planes[3].lines[0]
    line.events = [e for e in line.events if e.name != "window"]
    line.events += [ev("window", 2000 * i, 2000 * i + 1000)
                    for i in range(windows)]
    with pytest.raises(RuntimeError):
        sr.charge(p)


def recorded(name):
    from jax.profiler import ProfileData
    path = R.BENCH_DIR / "tests" / "data" / f"{name}.xplane.pb.gz"
    return ProfileData.from_serialized_xspace(
        gzip.decompress(path.read_bytes()))


def readings(trace, steps=2):
    return R.Readings(steps=steps, window_s=1.0, setup_s=0.0,
                      peak_bytes=0, build_seconds={}, least_s=0.0,
                      trace=trace)


def test_old_trace_has_no_program_span():
    spans = sr.charge(recorded("spmm_two_steps"))
    assert list(spans) == [sr.NONE]
    assert spans[sr.NONE]["launches"] == 28


@pytest.mark.parametrize("name", READERS)
def test_readers_are_silent_without_program_spans(name):
    read = R.load_module("metrics", name).read
    old = tr.reduce(recorded("spmm_two_steps"))   # as the harness has it
    assert read(readings(None)) is None
    assert read(readings(old)) is None
    old.spans = sr.charge(recorded("spmm_two_steps"))
    assert read(readings(old)) is None


@pytest.mark.parametrize("name,value", [
    ("vals_gather_ms", 1e3 * 75e-9 / 2),
    ("operand_prep_ms", 0.0),
    ("unpermute_ms", 0.0),
    ("launches_per_step", 1.0)])
def test_readers_on_the_split(name, value):
    r = tr.reduce(fake_profile())
    r.spans = sr.charge(fake_profile())
    got = R.load_module("metrics", name).read(readings(r))
    assert got == pytest.approx(value)


def test_span_split_adds_the_split_to_a_traced_run(tmp_path):
    from chipbench import span_split
    from chipbench.tests.cells import small_bench
    reduce = tr.reduce
    res = span_split.run_split(
        "pokec.spmm_fwd", 2**33 + 7, 0.2, bench=small_bench(tmp_path),
        root=tmp_path, cache_dir=tmp_path / "cache", require_chip=False,
        device_kind="TPU v5 lite")
    assert tr.reduce is reduce          # the harness's reduction is back
    assert res["correct"] is True
    assert set(res["split"]) == {*READERS, "kernel_span_ms",
                                 "charged_share"}
    assert res["spans"] == {}           # a CPU trace has no TPU plane


def test_recorded_trace_with_program_spans():
    """Two eager forwards of a 262,144-row power-law SpMM, d=128, traced
    on one TPU v5e with the benchmark's spans and the program's: the
    staged kernel runs as three calls in a scan, under its own name."""
    profile = recorded("spmm_spans_two_steps")
    r = tr.reduce(profile)
    assert dict(r.device_ops)["bcsr_fused_staged [kernel]"] == (
        pytest.approx(r.kernel_s))
    assert not any(k.startswith("closed_call") for k, _ in r.device_ops)
    spans = sr.charge(profile)
    assert spans == {
        "spmm.kernel": {"device_s": pytest.approx(0.667559612, rel=1e-6),
                        "launches": 2.0},
        "spmm.stage_vals": {"device_s": pytest.approx(0.096373633,
                                                      rel=1e-6),
                            "launches": 20.0},
        "spmm.unpermute": {"device_s": pytest.approx(0.005982243,
                                                     rel=1e-6),
                           "launches": 14.0}}
    charged = sum(v["device_s"] for k, v in spans.items() if k != sr.NONE)
    assert charged >= 0.99 * r.busy_s
    assert sum(v["device_s"] for v in spans.values()) == pytest.approx(
        r.busy_s, rel=1e-9)
    # the kernel's program holds the kernel and the scan's stacking
    assert spans["spmm.kernel"]["device_s"] == pytest.approx(r.kernel_s,
                                                             rel=0.01)
