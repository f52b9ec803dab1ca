"""Charge each device program of a traced window to the program span
that launched it.

The program opens fixed-name host spans around the stages of its call
path (``spmm.call``, ``spmm.stage_vals``, ``attn.kernel``, ...; see
``repro.kernels.ops.span``).  An eager call launches one XLA program per
operation from the Python thread, inside whatever spans are open then.
The profiler links each execution on a chip to its launch by ids, not
by time (read from traces of a TPU v5e by hand):

    chip   ``XLA Modules`` event              run_id, _c = F
    host   ``DoEnqueueProgram``               run_id, _p = F

``DoEnqueueProgram`` runs on the Python thread, or later on a
``pjrt-tpu-tasks`` thread.  There it lies inside a
``tpu::System::Execute=>IssueSequencedEvent`` event (``_c = G``) whose
producer, ``tpu::System::Execute`` (``_p = G``), ran on the Python
thread during the launch.  The Python thread has two lines: one with
the spans and ``PjitFunction`` events, one with the runtime's events,
joined by the ``PJRT_LoadedExecutable_Execute linkage`` flows.

So a module's chain of flows is followed back until it reaches the
Python thread, and the module is charged to the innermost program span
(``spmm.``, ``attn.``, ``spmm_batched.``) open there at that moment;
one with no such span, or no chain, goes under ``(none)``.  Module
times are clipped to the window span as ``trace_reduce`` clips them, so
the device seconds of all entries sum to its busy time.

    charge(profile) -> {span: {"device_s": s, "launches": n}}

averaged over the chips that ran anything, most device time first.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses

PREFIXES = ("spmm.", "attn.", "spmm_batched.")
NONE = "(none)"
DEVICE_PREFIX = "/device:TPU:"
MAX_HOPS = 8        # flows followed from a module back to its launch


@dataclasses.dataclass
class Event:
    name: str
    start: float        # ns
    end: float          # ns
    line: str
    stats: dict


def _line_events(line) -> list:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  line.name, dict(e.stats)) for e in line.events]


class _Launches:
    """The host's flow graph: from a module's ``_c`` to the time its
    launch ran on the Python thread."""

    def __init__(self, host_lines: dict, span_line: str):
        self.producers = {}
        consumers = collections.defaultdict(list)
        for events in host_lines.values():
            for e in events:
                if "_p" in e.stats:
                    self.producers.setdefault(e.stats["_p"], e)
                if "_c" in e.stats:
                    consumers[e.line].append(e)
        # the Python thread: the spans' line, and the lines its flows
        # lead to directly (the same thread's runtime events)
        self.thread = {span_line}
        for line, events in consumers.items():
            if any(self.producers.get(e.stats["_c"]) is not None
                   and self.producers[e.stats["_c"]].line == span_line
                   for e in events):
                self.thread.add(line)
        self.consumers = {}
        for line, events in consumers.items():
            events.sort(key=lambda e: e.start)
            self.consumers[line] = (events, [e.start for e in events])

    def _enclosing(self, e: Event):
        """The innermost flow consumer on ``e``'s line that holds the
        start of ``e`` (``e`` itself included)."""
        events, starts = self.consumers.get(e.line, ((), []))
        for i in range(bisect.bisect_right(starts, e.start) - 1, -1, -1):
            if events[i].end >= e.start:
                return events[i]
        return None

    def time(self, flow):
        """When the launch of the module consuming ``flow`` ran on the
        Python thread, or None where the chain breaks."""
        e = self.producers.get(flow)
        for _ in range(MAX_HOPS):
            if e is None:
                return None
            if e.line in self.thread:
                return e.start
            c = self._enclosing(e)
            if c is None:
                return None
            e = self.producers.get(c.stats["_c"])
        return None


def _innermost(spans, starts, t) -> str:
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[i].end > t:
            return spans[i].name
    return NONE


def charge(profile, window_span: str = "window") -> dict:
    """Device seconds and launches inside the host span ``window_span``
    by the program span that launched them (see the module docstring)."""
    host_lines, chips = {}, []
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            for ln in plane.lines:
                host_lines[ln.name] = _line_events(ln)
        elif plane.name.startswith(DEVICE_PREFIX):
            chips.append([e for ln in plane.lines
                          if ln.name == "XLA Modules"
                          for e in _line_events(ln)])
    windows = [(name, e) for name, events in host_lines.items()
               for e in events if e.name == window_span]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {window_span!r} span, found "
                           f"{len(windows)}")
    span_line, window = windows[0]
    lo, hi = window.start, window.end
    spans = sorted((e for e in host_lines[span_line]
                    if e.name.startswith(PREFIXES)),
                   key=lambda e: e.start)
    starts = [e.start for e in spans]
    launches = _Launches(host_lines, span_line)
    acc = collections.defaultdict(lambda: [0.0, 0])
    used = 0
    for modules in chips:
        inside = [m for m in modules if m.end > lo and m.start < hi]
        if not inside:
            continue
        used += 1
        for m in inside:
            t = launches.time(m.stats.get("_c"))
            name = NONE if t is None else _innermost(spans, starts, t)
            acc[name][0] += min(m.end, hi) - max(m.start, lo)
            acc[name][1] += 1
    used = max(used, 1)
    return {name: {"device_s": ns * 1e-9 / used, "launches": n / used}
            for name, (ns, n) in sorted(acc.items(),
                                        key=lambda kv: -kv[1][0])}
