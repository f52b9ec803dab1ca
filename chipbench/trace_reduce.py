"""Reduce a JAX profiler trace of one measured window to device busy
time, kernel time and a breakdown.

What a TPU trace holds (read from one by hand): each chip is a plane
``/device:TPU:<i>``.  Its ``XLA Modules`` line has one event per
executed program, and programs on one chip do not overlap.  Its
``XLA Ops`` line has the HLO operations, nested (a ``while`` holds its
body's operations); an event's name is the HLO instruction text, and a
Pallas kernel is a ``custom-call`` whose text names
``custom_call_target="tpu_custom_call"``.  The host plane ``/host:CPU``
has a line per thread; the benchmark's ``TraceAnnotation`` spans are
on its main thread's line, on the same clock as the device events.

    busy     union of the device's program intervals inside the window
    kernel   summed duration of the Pallas kernel events in the window
    glue     busy - kernel: every device operation that is not a kernel
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import shutil

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
DEVICE_PREFIX = "/device:TPU:"


@dataclasses.dataclass
class Event:
    name: str
    start: float        # ns
    end: float          # ns


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float       # averaged over the chips traced
    kernel_s: float
    glue_s: float
    device_ops: list    # [[label, seconds], ...] most self time first
    idle_gaps: list     # [[host span, seconds], ...] most idle time first


def load(trace_dir: str):
    """The ``ProfileData`` of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    return ProfileData.from_file(paths[0])


def _events(line) -> list:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def planes(profile, window_span: str = "window"):
    """``(device lines by chip, host events)`` as plain events:
    ``{chip: {"modules": [...], "ops": [...]}}``, and the events of the
    host thread that opened ``window_span``."""
    chips, host = {}, []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            chips[plane.name] = {
                "modules": _events(lines["XLA Modules"])
                if "XLA Modules" in lines else [],
                "ops": _events(lines["XLA Ops"])
                if "XLA Ops" in lines else []}
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                events = _events(ln)
                if any(e.name == window_span for e in events):
                    host.extend(events)
    return chips, host


def _clip(events, lo, hi):
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union(events) -> list:
    """Merged ``[start, end]`` intervals of the events."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return out


def self_times(ops) -> collections.Counter:
    """Self time of each operation label (its duration less that of the
    operations nested directly in it), summed by label."""
    acc = collections.Counter()
    stack = []                              # [event, children time]
    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            done, child = stack.pop()
            acc[op_label(done.name)] += (done.end - done.start) - child
        if stack:
            stack[-1][1] += e.end - e.start
        stack.append([e, 0.0])
    for done, child in stack:
        acc[op_label(done.name)] += (done.end - done.start) - child
    return acc


def op_label(hlo_text: str) -> str:
    """``%name = ...`` -> ``name``, with ``[kernel]`` on Pallas kernels
    and any ``.N`` suffix dropped so one operation keeps one label."""
    name = hlo_text.split(" = ", 1)[0].lstrip("%").strip()
    base, _, suffix = name.rpartition(".")
    if base and suffix.isdigit():
        name = base
    return name + (" [kernel]" if KERNEL_MARK in hlo_text else "")


def innermost(spans, starts, t):
    """Name of the innermost host span open at time ``t``: the latest
    to start of those that contain it.  ``spans`` are sorted by start
    and ``starts`` are their starts."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[i].end > t:
            return spans[i].name
    return "(no span)"


def reduce(profile, window_span: str = "window", top: int = 10) -> Reduced:
    """Busy, kernel and glue seconds inside the host span
    ``window_span``, averaged over the chips that ran anything, and the
    breakdown: device operations by self time, and idle time inside the
    window by the innermost host span open when the device went idle."""
    chips, host = planes(profile, window_span)
    windows = [e for e in host if e.name == window_span]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {window_span!r} span, found "
                           f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    spans = sorted((e for e in host if e is not windows[0]
                    and e.end > lo and e.start < hi), key=lambda e: e.start)
    starts = [e.start for e in spans]
    busy = kernel = 0.0
    ops_self = collections.Counter()
    idle = collections.Counter()
    used = 0
    for lines in chips.values():
        mods = _clip(lines["modules"], lo, hi)
        if not mods:
            continue
        used += 1
        intervals = union(mods)
        busy += sum(b - a for a, b in intervals)
        ops = _clip(lines["ops"], lo, hi)
        kernel += sum(e.end - e.start for e in ops
                      if KERNEL_MARK in e.name)
        ops_self.update(self_times(ops))
        edges = [lo] + [t for iv in intervals for t in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[innermost(spans, starts, a)] += b - a
    used = max(used, 1)
    busy, kernel = busy / used, kernel / used
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
        kernel_s=kernel * 1e-9, glue_s=max(busy - kernel, 0.0) * 1e-9,
        device_ops=[[k, v * 1e-9 / used]
                    for k, v in ops_self.most_common(top)],
        idle_gaps=[[k, v * 1e-9 / used] for k, v in idle.most_common(top)])


def reduce_dir(trace_dir: str) -> Reduced:
    """Reduce the profile written to ``trace_dir``, then delete it."""
    try:
        return reduce(load(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
