"""The reduction from a profiler trace to busy, kernel and glue time
and the breakdown."""
import dataclasses

import pytest

from chipbench import run as R

tr = R.load_module(".", "trace_reduce")

KERNEL = ('%k.1 = f32[8,128] custom-call(f32[8] %a), '
          'custom_call_target="tpu_custom_call"')


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


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


def ev(name, a, b):
    return Ev(name, a, b - a)


def fake_profile():
    """Two steps in a 1,000 ns window.  Step one: a gather (100-160),
    then a kernel (160-300) in one program (100-300).  Step two: a
    while loop (550-900) in one program, holding a kernel (600-800)."""
    device = Plane("/device:TPU:0", [
        Line("XLA Modules", [ev("jit_a(1)", 100, 300),
                             ev("jit_b(2)", 550, 900)]),
        Line("XLA Ops", [ev("%gather.3 = f32[9] gather(...)", 100, 160),
                         ev(KERNEL, 160, 300),
                         ev("%while.2 = (s32[]) while(...)", 550, 900),
                         ev(KERNEL, 600, 800)]),
    ])
    host = Plane("/host:CPU", [Line("python", [
        ev("window", 0, 1000), ev("step", 0, 500), ev("step", 500, 1000),
        ev("plan", -500, -100)])])
    return Profile([Plane("/host:metadata", []), device, host])


def test_busy_kernel_and_glue():
    r = tr.reduce(fake_profile())
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx((200 + 350) * 1e-9)
    assert r.kernel_s == pytest.approx((140 + 200) * 1e-9)
    assert r.glue_s == pytest.approx((60 + 150) * 1e-9)


def test_breakdown_by_self_time_and_by_host_span():
    r = tr.reduce(fake_profile())
    ops = dict(r.device_ops)
    assert ops["k [kernel]"] == pytest.approx(340e-9)
    assert ops["while"] == pytest.approx(150e-9)       # 350 less its kernel
    assert ops["gather"] == pytest.approx(60e-9)
    idle = dict(r.idle_gaps)
    # idle 0-100 and 300-500 in step one, 500-550 and 900-1000 in two
    assert idle == {"step": pytest.approx(450e-9)}


def test_window_must_be_traced_once():
    p = fake_profile()
    p.planes[2].lines[0].events.append(ev("window", 2000, 3000))
    with pytest.raises(RuntimeError):
        tr.reduce(p)


def test_op_labels():
    assert tr.op_label("%fusion.12 = f32[2] fusion(...)") == "fusion"
    assert tr.op_label(KERNEL) == "k [kernel]"
    assert tr.op_label("%copy-start = (f32[1]) copy-start()") == "copy-start"


def recorded_profile():
    """Two eager forwards of a 4,096-row power-law SpMM, d=128, traced
    on one TPU v5e with the benchmark's spans (``window``, ``step``)."""
    import gzip

    from jax.profiler import ProfileData
    path = R.BENCH_DIR / "tests" / "data" / "spmm_two_steps.xplane.pb.gz"
    return ProfileData.from_serialized_xspace(
        gzip.decompress(path.read_bytes()))


def test_recorded_trace():
    profile = recorded_profile()
    chips, host = tr.planes(profile)
    assert list(chips) == ["/device:TPU:0"]
    assert sum(e.name == "step" for e in host) == 2
    r = tr.reduce(profile)
    # two kernel events of about 2.15 ms, one per step
    assert r.kernel_s == pytest.approx(0.004302526, rel=1e-6)
    assert r.busy_s == pytest.approx(0.005284908, rel=1e-6)
    assert r.glue_s == pytest.approx(r.busy_s - r.kernel_s)
    assert 0 < r.busy_s < r.window_s == pytest.approx(0.016498479, rel=1e-6)
    ops = dict(r.device_ops)
    assert ops["spmm_bcsr_fused_staged [kernel]"] == pytest.approx(
        r.kernel_s)
    # every idle second of the window is attributed to some host span
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
