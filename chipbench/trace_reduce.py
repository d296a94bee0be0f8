"""Reduction of a profiler trace (``.xplane.pb``) to device time.

The trace holds one plane per TPU (``/device:TPU:<n>``) whose ``XLA Ops``
line has one event per operation run, and whose ``XLA Modules`` line has
one event per program run; and the host plane, whose thread lines hold
the harness's ``TraceAnnotation`` spans.  Every timestamp is on the
trace's one clock.

Programs are found by the names ``jax.jit`` gives them, followed by the
program's fingerprint: ``jit_decode(...)`` for ``ModelServer``'s decode
step and ``jit__lambda(...)`` for its prefill, a lambda whose name
changes if the program names it.  An operation is named by the program
it ran in and its HLO name (``jit_decode(123):%while.2``), and counted
by its self time: its duration less that of the operations nested in it
(a loop's body runs inside the loop's own event).
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
PROGRAMS = {"decode": "jit_decode(", "prefill": "jit__lambda("}
HOST_SPAN_PREFIXES = ("wait.", "invoke.", "exec.", "window")
CLOCK_SKEW_NS = 10_000_000


@dataclass
class Device:
    name: str
    busy: List[Tuple[int, int]]                   # merged (start, end) ns
    op_ns: Dict[str, int]                         # op name -> total ns
    modules: List[Tuple[str, int, int]]           # (name, start, dur) ns

    @property
    def busy_ns(self) -> int:
        return sum(e - s for s, e in self.busy)


@dataclass
class TraceSummary:
    window: Tuple[int, int]                       # ns
    devices: List[Device]
    host_spans: List[Tuple[str, int, int]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return (sum(d.busy_ns for d in self.devices) / len(self.devices)
                * 1e-9)

    def program_ns(self, program: str) -> List[int]:
        """Device durations of each run of ``program`` ("decode",
        "prefill") on the first chip."""
        key = PROGRAMS[program]
        return [dur for name, _, dur in self.devices[0].modules
                if name.startswith(key)]

    def top_ops(self, n: int = 10) -> List[list]:
        total: Dict[str, int] = defaultdict(int)
        for d in self.devices:
            for name, ns in d.op_ns.items():
                total[name] += ns
        k = len(self.devices)
        top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / k * 1e-9] for name, ns in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle time on the first chip inside the window, summed by the
        innermost host span open at the middle of each gap."""
        spans = sorted((hs, he, name) for name, hs, he in self.host_spans
                       if name != "window")
        starts = [hs for hs, _, _ in spans]
        by: Dict[str, int] = defaultdict(int)
        for s, e in gaps(self.devices[0].busy, self.window):
            mid = (s + e) // 2
            name = "none"
            hi = bisect.bisect_right(starts, mid) - 1
            # spans nest at most two deep (invoke around exec), so the
            # open one, if any, is among the last few to start
            for i in range(hi, max(hi - 8, -1), -1):
                if spans[i][1] > mid:
                    name = spans[i][2]
                    break
            by[name] += e - s
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9] for name, ns in top]


def merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, window):
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Tuple[int, int]], window: Tuple[int, int]):
    out, t = [], window[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def op_name(hlo: str) -> str:
    """``%while.2`` from ``%while.2 = (s32[], ...) while(...)``."""
    return hlo.split(" = ", 1)[0]


def self_times(ops, modules) -> Dict[str, int]:
    """Self time of each operation, summed by program and HLO name."""
    ops = sorted(ops, key=lambda o: (o[0], -o[1]))
    starts = [s for _, s, _ in modules]
    own: Dict[int, int] = {}
    stack: List[int] = []
    for i, (s, e, _) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        own[i] = e - s
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    out: Dict[str, int] = defaultdict(int)
    for i, (s, _, name) in enumerate(ops):
        m = bisect.bisect_right(starts, s) - 1
        prog = modules[m][0] if m >= 0 and s < starts[m] + modules[m][2] \
            else "?"
        out[f"{prog}:{op_name(name)}"] += own[i]
    return dict(out)


def find(directory: str) -> Optional[str]:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def reduce(path: str, window_span: str = "window") -> TraceSummary:
    """Device busy time, op totals and program runs inside the host span
    named ``window_span``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = []
    window = None
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_SPAN_PREFIXES):
                    host.append((ev.name, int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns)))
                    if ev.name == window_span and window is None:
                        window = (int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns))
    if window is None:
        raise ValueError(f"no {window_span!r} span in {path}")
    devices = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    if s + d > window[0] and s < window[1]:
                        ops.append((s, s + d, ev.name))
            elif line.name == MODULES_LINE:
                # the device's clock reads up to a few ms behind the
                # host's, so the window's first program may show as
                # starting "before" the window's host span
                for ev in line.events:
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    if window[0] - CLOCK_SKEW_NS <= s < window[1]:
                        modules.append((ev.name, s, d))
        if ops or modules:
            devices.append(Device(
                plane.name, clip(merge([(s, e) for s, e, _ in ops]), window),
                self_times(ops, modules), modules))
    if not devices:
        raise ValueError(f"no device operations in {path}")
    return TraceSummary(window, devices, host)
