"""The program's own spans in a profiler trace, and the chip's idle time
put down to them.

The program opens ``repro.<name>`` spans (``repro.obs.span``) where the
sweep scheduler and the serve engine do their host work.  They land on
the host plane's Python thread, on the clock of the device's ``XLA Ops``
(``trace.py``), so each stretch of idle time on the first chip can be
put down to the innermost program span open at that instant:

* the window is ``trace.py``'s (the harness span ``chipbench.window``,
  or the whole trace);
* ``spans``: each ``repro.*`` name -> [count, seconds] of its spans that
  overlap the window, clipped to it;
* ``idle``: idle seconds of the first chip inside the window by the
  innermost ``repro.*`` span open (the one that started last), or ``no
  program span``.  An idle interval is cut at every span boundary it
  crosses, so one long gap may land on several spans.  Harness spans
  (``chipbench.*``) are not program spans and label nothing here.

``trace.summarize`` reads the same planes; nothing it reports changes.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq

from chipbench import trace

PROGRAM_PREFIX = "repro."
NO_SPAN = "no program span"


@dataclasses.dataclass
class ProgramSpans:
    window_s: float
    spans: dict      # repro.* name -> [count, seconds] inside the window
    idle: dict       # innermost repro.* span (or NO_SPAN) -> idle seconds

    def idle_under(self, names) -> float | None:
        """Idle seconds under any of ``names``; None where none of them
        opened in the window (a program without those spans)."""
        names = [n for n in names if n in self.spans]
        if not names:
            return None
        return sum(self.idle.get(n, 0.0) for n in names)


def _program_spans(profile):
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return out


def _window(profile, devices):
    windows = [(s, e) for s, e, n in trace._host_spans(profile)
               if n == trace.WINDOW_SPAN]
    if windows:
        return min(s for s, _ in windows), max(e for _, e in windows)
    evs = [ev for plane in devices for line in plane.lines
           if line.name == trace.OPS_LINE for ev in line.events]
    if not evs:
        raise ValueError("the trace holds no device operation")
    return (min(ev.start_ns for ev in evs),
            max(ev.start_ns + ev.duration_ns for ev in evs))


def _idle(busy, w0, w1):
    """Idle intervals of the window around merged busy intervals."""
    prev = w0
    for s, e in busy + [[w1, w1]]:
        s, e = max(s, w0), min(e, w1)
        if s > prev:
            yield prev, s
        prev = max(prev, e)


def label_idle(idle, spans) -> dict:
    """Idle seconds by the innermost span open, each idle interval cut
    at the span boundaries inside it.  ``idle``: (start, end) in time
    order; ``spans``: (start, end, name)."""
    bounds = sorted({t for s, e, _ in spans for t in (s, e)})
    order = sorted(spans)
    out = {}
    heap = []                # (-start, end, name): latest start on top
    nxt = 0
    for a, b in idle:
        cuts = bounds[bisect.bisect_right(bounds, a):
                      bisect.bisect_left(bounds, b)]
        for x, y in zip([a] + cuts, cuts + [b]):
            mid = 0.5 * (x + y)
            while nxt < len(order) and order[nxt][0] <= mid:
                s, e, n = order[nxt]
                heapq.heappush(heap, (-s, e, n))
                nxt += 1
            while heap and heap[0][1] <= mid:
                heapq.heappop(heap)
            label = heap[0][2] if heap else NO_SPAN
            out[label] = out.get(label, 0.0) + (y - x) * 1e-9
    return out


def summarize(profile) -> ProgramSpans:
    devices = [p for p in profile.planes if trace.DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    w0, w1 = _window(profile, devices)
    busy = trace._merge([
        (max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1))
        for line in devices[0].lines if line.name == trace.OPS_LINE
        for ev in line.events
        if ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0])
    spans = [(max(s, w0), min(e, w1), n) for s, e, n in _program_spans(profile)
             if s < w1 and e > w0]
    totals = {}
    for s, e, n in spans:
        c = totals.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-9
    return ProgramSpans(window_s=(w1 - w0) * 1e-9, spans=totals,
                        idle=label_idle(list(_idle(busy, w0, w1)), spans))


def load(trace_dir_or_file: str) -> ProgramSpans:
    from jax.profiler import ProfileData
    path = (trace_dir_or_file if trace_dir_or_file.endswith(".xplane.pb")
            else trace.find_xplane(trace_dir_or_file))
    return summarize(ProfileData.from_file(path))
