"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device busy
time, per-op and per-program device time, and idle gaps by host span.

Read with ``jax.profiler.ProfileData``.  A device is a plane named
``/device:TPU:<i>``; its ``XLA Ops`` line holds one event per executed
operation and its ``XLA Modules`` line one per executed program
(``jit_<name>(<id>)``).  The host's annotations are events of the host
plane; the benchmark opens its own, named ``bench/...``, around each
host step, and one ``bench/window`` around the traced stretch.

Busy time is the union of a device's op intervals inside the stretch,
averaged over devices; program busy time leaves out the ops that run
inside the benchmark's own programs (``jit_bench_*``), since a device
runs one program at a time; an idle gap is a stretch of the window in which
no op of that device runs, charged to the innermost ``bench/`` span
open at the gap's midpoint ("(no span)" where none is).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench/window"
SPAN_PREFIX = "bench/"
BENCH_PROGRAM_PREFIX = "jit_bench_"


@dataclass
class TraceSummary:
    devices: int
    window_s: float
    busy_s: float                               # mean over devices
    program_busy_s: float                       # the same, benchmark programs left out
    ops: List[Tuple[str, float]]                # per op name, mean over devices
    programs: Dict[str, float]                  # per program name, mean over devices
    gaps: List[Tuple[str, float]]               # idle seconds per host span, mean
    longest_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, bench: bool = False) -> float:
        """Device seconds of the program under test's programs (or, with
        ``bench=True``, of the benchmark's own)."""
        return sum(s for name, s in self.programs.items()
                   if name.startswith(BENCH_PROGRAM_PREFIX) == bench)


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _program_name(event_name: str) -> str:
    return re.sub(r"\(\d+\)$", "", event_name)


def _op_name(event_name: str) -> str:
    """``%sort.32 = (...) sort(...)`` -> ``sort.32``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def reduce_trace(path: str) -> Optional[TraceSummary]:
    """Summarize one trace file; ``None`` where it holds no device op."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, window = [], None
    devices: Dict[int, Dict[str, list]] = {}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: list(_events(ln)) for ln in plane.lines}
            devices[int(m.group(1))] = lines
            continue
        for line in plane.lines:
            for name, t0, dur in _events(line):
                if name == WINDOW_SPAN:
                    window = (t0, t0 + dur)
                elif name.startswith(SPAN_PREFIX):
                    spans.append((t0, t0 + dur, name))
    ops_by_dev = {d: lines.get("XLA Ops") or lines.get("XLA Modules") or []
                  for d, lines in devices.items()}
    if not any(ops_by_dev.values()):
        return None
    if window is None:
        starts = [t for evs in ops_by_dev.values() for _, t, _ in evs]
        ends = [t + d for evs in ops_by_dev.values() for _, t, d in evs]
        window = (min(starts), max(ends))
    w0, w1 = window
    nd = len(devices)
    busy, pbusy, ops, programs, gaps, longest = 0.0, 0.0, {}, {}, {}, []
    spans.sort()
    for d, lines in devices.items():
        evs = [(n, max(t, w0), min(t + du, w1)) for n, t, du in ops_by_dev[d]]
        evs = [(n, a, b) for n, a, b in evs if b > a]
        for n, a, b in evs:
            n = _op_name(n)
            ops[n] = ops.get(n, 0.0) + (b - a) / 1e9 / nd
        own = []
        for n, t, du in lines.get("XLA Modules", []):
            a, b = max(t, w0), min(t + du, w1)
            if b > a:
                p = _program_name(n)
                programs[p] = programs.get(p, 0.0) + (b - a) / 1e9 / nd
                if p.startswith(BENCH_PROGRAM_PREFIX):
                    own.append((a, b))
        merged = _merge([(a, b) for _, a, b in evs])
        busy += sum(b - a for a, b in merged) / 1e9 / nd
        own = _merge(own)
        starts = [a for a, _ in own]
        theirs = []
        for _, a, b in evs:
            i = bisect.bisect_right(starts, (a + b) / 2) - 1
            if i < 0 or own[i][1] < (a + b) / 2:
                theirs.append((a, b))
        pbusy += sum(b - a for a, b in _merge(theirs)) / 1e9 / nd
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        # one sweep over gaps and spans, both in time order: the spans
        # open at a gap's midpoint are a stack (they nest), its top the
        # innermost
        stack, j = [], 0
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            while j < len(spans) and spans[j][0] <= mid:
                stack.append(spans[j])
                j += 1
            while stack and stack[-1][1] < mid:
                stack.pop()
            label = stack[-1][2] if stack else "(no span)"
            gaps[label] = gaps.get(label, 0.0) + (b - a) / 1e9 / nd
            longest.append((label, (b - a) / 1e9))
    longest.sort(key=lambda x: -x[1])
    return TraceSummary(
        devices=nd, window_s=(w1 - w0) / 1e9, busy_s=busy, program_busy_s=pbusy,
        ops=sorted(ops.items(), key=lambda x: -x[1]),
        programs=programs,
        gaps=sorted(gaps.items(), key=lambda x: -x[1]),
        longest_gaps=longest[:10])
