"""Device idle time charged to the host span open at each moment of it.

The second attribution of a profiler trace, beside the gaps of
``xplane.reduce_trace`` (whole gaps charged to the benchmark's span at
their midpoint):

* idle is what ``device_idle.stream`` counts: the stretch of
  ``bench/window`` in which no op of the program under test runs on a
  device; ops inside the benchmark's own programs (``jit_bench_*``)
  count as idle;
* the spans are those of the host thread that holds ``bench/window``
  whose names are span names, lowercase words joined by ``/`` (the
  program's ``repro.obs`` spans, ``plan/rhg``, ``wave/rows``, and the
  benchmark's, ``bench/next_chunk``); that thread's other events
  (``PjitFunction(...)``, the runtime's own) are not spans;
* every idle nanosecond is charged to the spans open at it, as the path
  from the outermost to the innermost, ``()`` where none is.

The seconds of all paths sum to the idle time, averaged over devices.
The program's spans are in the trace only where ``repro.obs`` was on
while the profiler ran (each enabled span is a ``TraceAnnotation``).
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from .xplane import (BENCH_PROGRAM_PREFIX, WINDOW_SPAN, _DEVICE_PLANE, _events,
                     _merge, _program_name)

SPAN_NAME = re.compile(r"^[a-z0-9_]+(/[a-z0-9_]+)+$")
NO_SPAN = "(no span)"
#: the runtime's and the front door's per-wave host work
WAVE_HOST = ("wave/dispatch", "wave/sink", "wave/rows", "stream/chunk")
#: the benchmark's span around ``next()`` on the program's stream
NEXT_CHUNK = "bench/next_chunk"

Path = Tuple[str, ...]
Interval = Tuple[float, float]


@dataclass
class IdleAttribution:
    window_s: float
    idle_s: float                       # mean over devices
    paths: Dict[Path, float]            # idle seconds per open-span path, mean

    def labels(self) -> List[Tuple[str, float]]:
        """Idle seconds by the innermost span open, largest first."""
        out: Dict[str, float] = {}
        for path, s in self.paths.items():
            label = path[-1] if path else NO_SPAN
            out[label] = out.get(label, 0.0) + s
        return sorted(out.items(), key=lambda x: -x[1])

    def seconds(self, where: Callable[[Path], bool]) -> float:
        return sum(s for path, s in self.paths.items() if where(path))

    def share(self, where: Callable[[Path], bool]) -> float:
        """Percent of the traced stretch idle under paths ``where`` holds."""
        return 100.0 * self.seconds(where) / self.window_s

    # the groups a stream cell's idle time is read in
    def plan_share(self) -> float:
        return self.share(lambda p: any(n.startswith("plan/") for n in p))

    def wave_host_share(self) -> float:
        return self.share(lambda p: bool(p) and p[-1] in WAVE_HOST)

    def unspanned_share(self) -> float:
        return self.share(lambda p: bool(p) and p[-1] == NEXT_CHUNK)


def program_busy(ops: Sequence[Tuple[str, float, float]],
                 modules: Sequence[Tuple[str, float, float]],
                 window: Interval) -> List[List[float]]:
    """Merged intervals of ``window`` in which an op of the program
    under test runs: ``(name, start, duration)`` ops and programs, an op
    left out where its midpoint lies in a ``jit_bench_*`` program (the
    intervals whose length ``xplane`` sums as ``program_busy_s``)."""
    w0, w1 = window
    own = _merge([(max(t, w0), min(t + d, w1)) for n, t, d in modules
                  if _program_name(n).startswith(BENCH_PROGRAM_PREFIX)
                  and min(t + d, w1) > max(t, w0)])
    starts = [a for a, _ in own]
    theirs = []
    for _, t, d in ops:
        a, b = max(t, w0), min(t + d, w1)
        if b <= a:
            continue
        i = bisect.bisect_right(starts, (a + b) / 2) - 1
        if i < 0 or own[i][1] < (a + b) / 2:
            theirs.append((a, b))
    return _merge(theirs)


def span_segments(spans: Sequence[Tuple[float, float, str]],
                  window: Interval) -> List[Tuple[float, float, Path]]:
    """Cut ``window`` at every span boundary; each piece carries the
    path of the spans open over it, outermost first (one thread's spans
    nest, so the one begun last is the innermost)."""
    w0, w1 = window
    spans = sorted(((max(a, w0), min(b, w1), n) for a, b, n in spans
                    if min(b, w1) > max(a, w0)), key=lambda s: (s[0], -s[1]))
    cuts = sorted({w0, w1} | {x for a, b, _ in spans for x in (a, b)})
    out, stack, j = [], [], 0
    for t0, t1 in zip(cuts, cuts[1:]):
        stack = [s for s in stack if s[1] > t0]
        while j < len(spans) and spans[j][0] <= t0:
            if spans[j][1] > t0:
                stack.append(spans[j])
            j += 1
        path = [n for _, _, n in stack]
        # a benchmark span opens a TraceAnnotation and, where repro.obs is
        # on, a span of the same name: one name, one step of the path
        out.append((t0, t1, tuple(n for i, n in enumerate(path)
                                  if not i or path[i - 1] != n)))
    return out


def charge(busy: Sequence[Sequence[float]], segments, window: Interval
           ) -> Dict[Path, float]:
    """Nanoseconds of ``window`` outside ``busy`` (merged, sorted), per
    path of the contiguous ``segments`` that cover the window."""
    w0, w1 = window
    edges = [w0] + [x for ab in busy for x in ab] + [w1]
    out: Dict[Path, float] = {}
    k = 0
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        while segments[k][1] <= a:
            k += 1
        m = k
        while m < len(segments) and segments[m][0] < b:
            s0, s1, path = segments[m]
            out[path] = out.get(path, 0.0) + min(b, s1) - max(a, s0)
            m += 1
    return out


def attribute(devices, spans, window: Interval) -> IdleAttribution:
    """Attribute idle time given, per device, its ``(ops, modules)``
    events as ``(name, start_ns, duration_ns)``, and the thread's spans
    as ``(start_ns, end_ns, name)``."""
    segments = span_segments(spans, window)
    nd = len(devices)
    paths: Dict[Path, float] = {}
    for ops, modules in devices:
        for p, ns in charge(program_busy(ops, modules, window), segments,
                            window).items():
            paths[p] = paths.get(p, 0.0) + ns / 1e9 / nd
    return IdleAttribution(window_s=(window[1] - window[0]) / 1e9,
                           idle_s=sum(paths.values()), paths=paths)


def idle_by_span(path: str) -> Optional[IdleAttribution]:
    """Attribute one trace file's device idle time; ``None`` where it
    holds no device op or no ``bench/window``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window, spans, devices = None, [], []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: list(_events(ln)) for ln in plane.lines}
            ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
            devices.append((ops, lines.get("XLA Modules", [])))
            continue
        for line in plane.lines:
            evs = list(_events(line))
            held = [(t, t + d) for n, t, d in evs if n == WINDOW_SPAN]
            if held:
                # the last, as xplane reads it: with repro.obs on, the
                # benchmark's span is there twice, one inside the other
                window = held[-1]
                spans = [(t, t + d, n) for n, t, d in evs
                         if n != WINDOW_SPAN and SPAN_NAME.match(n)]
    if window is None or not any(ops for ops, _ in devices):
        return None
    return attribute(devices, spans, window)
