"""From a JAX profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes.  On a
TPU it holds one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Ops`` lists every operation the TensorCore ran, one after another,
each named by its HLO instruction (``%junction_fwd.2 = f32[...]
custom-call(...)``).  The host plane ``/host:CPU`` holds, on the Python
thread's line, the spans the harness opened with
``jax.profiler.TraceAnnotation`` (names starting ``chipbench.``).  Both
are on one clock, in nanoseconds.

The reduction:

* the window is the harness span ``chipbench.window`` (the measured
  window), or the whole trace where that span is absent;
* busy time is the union of the ``XLA Ops`` intervals inside the window,
  averaged over the chips; idle is the window less busy;
* a jitted program's device time is the sum of its ``XLA Modules``
  events (``jit_<function>(<fingerprint>)``) inside the window;
* an operation's name is its HLO name without ``%`` and the ``.<n>``
  suffix, so a ``pallas_call`` shows under its stable kernel name
  (``junction_fwd``, ``junction_update_dw``, ``flash_decode``);
* an operation's time is its own: an enclosing ``while`` loses the time
  of the operations listed inside it, so per-operation times add up to
  the busy time;
* each idle gap is labelled with the innermost harness span open at its
  midpoint, or ``no harness span``.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def _self_times(intervals):
    """(name, seconds) of each operation less the operations it encloses
    (a ``while`` or ``conditional`` lists its body's operations on the
    same line, inside its own interval)."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][0], -intervals[i][1]))
    own = [e - s for s, e, _ in intervals]
    stack = []
    for i in order:
        s, e, _ = intervals[i]
        while stack and intervals[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= intervals[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(intervals[i][2], max(own[i], 0) * 1e-9)
            for i in range(len(intervals))]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class TraceSummary:
    chips: int
    window_s: float
    busy_s: float                      # averaged over the chips
    op_s: dict                         # op name -> device seconds, summed over chips
    op_count: dict                     # op name -> events
    gaps: list                         # [(label, seconds)], longest first
    spans: dict                        # harness span name -> [count, seconds]
    module_s: dict = dataclasses.field(default_factory=dict)
    # jitted program (XLA module, e.g. jit_prefill_chunk) -> device
    # seconds, summed over chips

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, prefix: str) -> float:
        return sum(v for k, v in self.op_s.items() if k.startswith(prefix))

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:n]]}


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _host_spans(profile):
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def summarize(profile, top_gaps: int = 10) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a TraceSummary."""
    spans = _host_spans(profile)
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    devices = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no /device:TPU plane")
    per_chip = []
    for plane in devices:
        evs = [ev for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        per_chip.append(evs)
    if windows:
        w0 = min(s for s, _ in windows)
        w1 = max(e for _, e in windows)
    else:
        starts = [ev.start_ns for evs in per_chip for ev in evs]
        ends = [ev.start_ns + ev.duration_ns for evs in per_chip for ev in evs]
        if not starts:
            raise ValueError("the trace holds no device operation")
        w0, w1 = min(starts), max(ends)
    op_s = collections.Counter()
    op_count = collections.Counter()
    module_s = collections.Counter()
    for plane in devices:
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e > s:
                    module_s[ev.name.split("(", 1)[0]] += (e - s) * 1e-9
    busy_total = 0.0
    merged0 = None
    for evs in per_chip:
        iv = []
        for ev in evs:
            s = max(ev.start_ns, w0)
            e = min(ev.start_ns + ev.duration_ns, w1)
            if e > s:
                iv.append((s, e, op_name(ev.name)))
        for name, secs in _self_times(iv):
            op_s[name] += secs
            op_count[name] += 1
        merged = _merge([(s, e) for s, e, _ in iv])
        busy_total += sum(e - s for s, e in merged) * 1e-9
        if merged0 is None:
            merged0 = merged
    # idle gaps of the first chip, labelled by the innermost host span
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW_SPAN]
    gaps = collections.Counter()
    prev = w0
    for s, e in (merged0 or []) + [[w1, w1]]:
        if s > prev:
            mid = 0.5 * (s + prev)
            open_ = [(e2 - s2, n) for s2, e2, n in inner if s2 <= mid <= e2]
            label = min(open_)[1] if open_ else "no harness span"
            gaps[label] += (s - prev) * 1e-9
        prev = max(prev, e)
    span_tot = {}
    for s, e, n in spans:
        c = span_tot.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-9
    return TraceSummary(
        chips=len(devices), window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / len(devices), op_s=dict(op_s),
        op_count=dict(op_count),
        gaps=sorted(gaps.items(), key=lambda kv: -kv[1])[:top_gaps],
        spans=span_tot, module_s=dict(module_s))


def load(trace_dir_or_file: str) -> TraceSummary:
    from jax.profiler import ProfileData
    path = (trace_dir_or_file if trace_dir_or_file.endswith(".xplane.pb")
            else find_xplane(trace_dir_or_file))
    return summarize(ProfileData.from_file(path))
