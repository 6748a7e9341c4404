"""Flight recorder: ONE telemetry layer for train, sweep, and serve.

Every subsystem that used to emit its own scattered signals — the train
loop's ``metrics`` dict, ``ContinuousEngine.stats``, the sweep ledger
prints, the percentiles computed privately inside
``benchmarks/serve_benches.py`` — records through a :class:`Recorder`
instead, so one run produces one machine-readable timeline that
``repro.launch.obs_report`` can render and future scale-out PRs can read
their numbers from.

The recorder carries three aggregate families plus an event stream:

* **counters** — monotonically increasing ints (``count``): steps run,
  requests finished per outcome, checkpoints written;
* **gauges** — latest-value floats (``gauge``): the current lr_scale;
* **histograms** — bounded sample windows (``observe``) with
  nearest-rank percentiles (:func:`percentile`): step latency, TTFT,
  inter-token latency, and each :func:`span`'s duration;
* **events** — typed frozen dataclasses (:class:`TrainStep`,
  :class:`Guardian`, :class:`Checkpoint`, :class:`RequestSpan`,
  :class:`SweepRound`) appended to a bounded in-memory ring and, when a
  ``path`` is given, streamed as one JSON line each (JSONL).  The sink
  opens with a ``meta`` header line and :meth:`Recorder.close` appends a
  ``summary`` line holding the final counters/gauges/histogram digests.

No-extra-device-sync contract
-----------------------------
The recorder is HOST-ONLY instrumentation.  It never forces a
``block_until_ready``, never adds a traced op, and never triggers a
device→host transfer of its own: producers hand it values the step
ALREADY returned to host (the ``float(metrics["loss"])`` the train loop
does for honest step timing, the ``np.asarray(tok)`` the serve scheduler
needs anyway).  This is enforced, not just documented — every recorded
value passes :func:`_ensure_host`, which raises ``TypeError`` on a
``jax.Array`` — and regression-tested: the jaxpr of a fused train step
is identical with and without a recorder attached, and
``ContinuousEngine`` still reports ``decode_traces == 1`` /
``prefill_traces == 1`` with telemetry on (tests/test_obs.py, the ci.sh
serve smoke).  A value a producer did not already sync is recorded as
the sentinel ``-1.0`` ("not sampled on this path"), never fetched.

Event schema
------------
Each JSONL line is ``{"kind": ..., "ts": ..., "seq": ..., **fields}``;
``kind`` names the dataclass (``train.step``, ``guardian``,
``checkpoint``, ``serve.span``, ``sweep.round``, plus the ``meta`` /
``summary`` frame lines).  ``seq`` is the per-recorder emission index,
``ts`` host wall-clock seconds.  ``read_events`` round-trips a file.

Span lifecycle (``serve.span``)
-------------------------------
One event per finished request, emitted by ``ContinuousEngine`` at
slot-free time, reconstructing the whole request timeline:
``enqueue_tick`` (arrival) → ``admit_tick`` (pages allocated, slot
taken) → ``prefill_chunks`` fixed-shape chunks → ``first_token_tick`` /
``ttft_s`` (sampled off the final prefill chunk's logits) →
``finish_tick`` with ``outcome`` ∈ {``eos``, ``max_new``, ``guard``}.
``ttft_s`` / ``first_token_tick`` are ``-1`` when the request never
produced a token (guard-terminated during prefill).  ``queue_s`` is the
wall time from the scheduler iteration in which the engine's tick
reached the request's ``arrival`` (the ``serve()`` entry, for arrival 0)
to its admission, so TTFT from arrival is ``queue_s + ttft_s``.

Spans
-----
:func:`span` is the program's one span API: a context manager that opens
``jax.profiler.TraceAnnotation("repro." + name)``.  Under a profiler
(``profile_ctx``, ``launch/* --profile``, or any ``jax.profiler.trace``)
the span lands in the trace's ``/host:CPU`` plane on the Python thread,
on the clock the device's ``XLA Ops`` use, so an idle gap on the device
can be put down to the host work open at that instant.  That clock is
the wall clock less the trace's ``profile_start_time`` (a stat of its
``Task Environment`` plane): a Recorder event's ``ts`` lands inside the
span it was emitted in.  With a Recorder, the span also observes its
duration into the histogram ``span.<name>_s``, so a run without a
profiler still shows where host time goes (``obs_report`` prints them).
Without a profiler a span costs one ``TraceAnnotation`` construction.

The no-extra-device-sync contract covers spans: a span never fetches,
never blocks and adds no traced op (jaxprs are identical inside and
outside one).  The names, and how they nest (an inner span is open
only inside its outer one):

* ``repro.sweep.setup`` — ``search/scheduler.run_sweep`` from entry to
  the first cohort step: the data's host→device copies and each
  cohort's weights, slots, hyp table, step and eval (from the program
  cache, built on first use) and padded targets;
* ``repro.sweep.first_step`` — a cohort's first step of the call (on
  a cold call also its trace, lowering and compile-cache fetch),
  ``repro.sweep.step`` —
  each later cohort step (hyp stamp, dispatch, bookkeeping); both hold
  ``repro.sweep.fetch``, the fetch of the step's losses and health;
* ``repro.sweep.eval`` — one cohort's eval in a round, its fetch
  included; ``repro.sweep.prune`` — rank, halve and the mask/hyp writes;
* ``repro.serve.setup`` — ``serve/engine.ContinuousEngine.serve``'s
  paged cache, slots and sorted queue;
* ``repro.serve.admit`` — one scheduler iteration's admission loop,
  page allocation included (one per iteration);
* ``repro.serve.prefill`` — one prefill chunk: its build, dispatch and
  the first token's sampling; ``repro.serve.decode`` — one decode
  tick: the host build of tokens, positions and page table, dispatch,
  per-slot bookkeeping and ``finish``; both hold ``repro.serve.fetch``,
  the fetch of the chunk's last logits or the tick's tokens.

Between two ticks the engine is always inside one of the serve spans
but for a few list comprehensions; the sweep leaves each global step's
batch gather outside its cohort spans.
"""
from __future__ import annotations

import dataclasses
import json
import math
import sys
import time
from collections import deque
from typing import Any, ClassVar, IO, Iterable, Optional

__all__ = [
    "Checkpoint", "Guardian", "Histogram", "Recorder", "RequestSpan",
    "SweepRound", "TrainStep", "percentile", "profile_ctx", "read_events",
    "span",
]

#: prefix of every program span in a profiler trace (``chipbench.`` is
#: the benchmark harness's own)
SPAN_PREFIX = "repro."

#: histogram value meaning "producer did not sync this value on this
#: path" — recorded instead of forcing a device→host transfer
NOT_SAMPLED = -1.0


def percentile(samples: Iterable[float], q: float) -> float:
    """Nearest-rank percentile: the q-th percentile of n samples is the
    ``ceil(q/100 * n)``-th smallest OBSERVED value.

    Unlike linear interpolation (``np.percentile``'s default), this never
    invents a value between samples, and the small-sample behavior is the
    honest one: p99 of fewer than 100 samples is the max — with 2 latency
    measurements there is no evidence for anything between them, and an
    SLO check must see the worst observed, not an interpolation past it.
    """
    xs = sorted(float(v) for v in samples)
    if not xs:
        raise ValueError("percentile of an empty sample set")
    if not 0.0 < q <= 100.0:
        raise ValueError(f"percentile q must be in (0, 100], got {q}")
    rank = math.ceil(q / 100.0 * len(xs))   # 1-based nearest rank
    return xs[max(rank, 1) - 1]


def profile_ctx(trace_dir: str | None):
    """``jax.profiler.trace`` context for the launchers' ``--profile
    <dir>`` flag (None: no-op).  Combined with the named scopes in
    kernels/ops.py and kernels/flash_attention.py, the resulting trace
    attributes device time to junction kernels by KernelSpec.  jax is
    imported lazily so ``--help`` paths stay jax-free."""
    import contextlib
    if trace_dir is None:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.trace(trace_dir)


_trace_annotation = None      # jax.profiler.TraceAnnotation, at first use


class _Span:
    __slots__ = ("_ann", "_rec", "_key", "_t0")

    def __init__(self, name: str, recorder: "Recorder | None"):
        global _trace_annotation
        if _trace_annotation is None:
            from jax.profiler import TraceAnnotation as _trace_annotation
        self._ann = _trace_annotation(SPAN_PREFIX + name)
        self._rec = recorder
        self._key = f"span.{name}_s" if recorder is not None else None

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        if self._rec is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._rec is not None:
            self._rec.observe(self._key, time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)


def span(name: str, recorder: "Recorder | None" = None) -> _Span:
    """A host span ``repro.<name>`` in the profiler's trace and, with a
    ``recorder``, a ``span.<name>_s`` duration sample (see the module
    docstring's "Spans" section).  Host-only: never fetches or blocks.
    jax is imported at first use, so ``--help`` paths stay jax-free."""
    return _Span(name, recorder)


def _ensure_host(name: str, v: Any) -> Any:
    """The no-extra-device-sync guard: recording a live ``jax.Array``
    would force a device→host transfer the step didn't already pay for —
    refuse it and make the producer convert at its own sync point.
    (Lazy ``sys.modules`` lookup: if jax was never imported there is
    nothing to guard, and ``--help`` paths stay jax-free.)"""
    jax = sys.modules.get("jax")
    if jax is not None and isinstance(v, jax.Array):
        raise TypeError(
            f"telemetry value {name!r} is a jax.Array — the recorder only "
            "consumes values already returned to host (no-extra-device-sync "
            "contract, obs/telemetry.py); convert with float()/int()/"
            "np.asarray() at the step's own sync point")
    return v


# ------------------------------------------------------------- event types
@dataclasses.dataclass(frozen=True)
class TrainStep:
    """One adopted train step (train/train_loop.py).  ``nonfinite`` is
    the in-kernel health count when the guardian already fetched it,
    else the ``NOT_SAMPLED`` sentinel."""
    KIND: ClassVar[str] = "train.step"
    step: int
    loss: float
    nonfinite: float
    lr_scale: float
    dt_s: float
    dt_ema_s: float
    tokens_per_s: float


@dataclasses.dataclass(frozen=True)
class Guardian:
    """Guardian lifecycle: ``action`` ∈ trip | rollback | backoff |
    recovery, in that order per incident.  ``step`` is the train-loop
    step the action refers to (trip: the step whose update was
    discarded; rollback/backoff/recovery: the healthy step training
    resumed from)."""
    KIND: ClassVar[str] = "guardian"
    action: str
    step: int
    detail: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Checkpoint:
    """Checkpoint lifecycle: ``action`` ∈ save | promote | gc (promote =
    the healthy mark after surviving the guardian's health window)."""
    KIND: ClassVar[str] = "checkpoint"
    action: str
    step: int
    detail: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class RequestSpan:
    """One finished serve request — the whole lifecycle in one event
    (see the module docstring's span section)."""
    KIND: ClassVar[str] = "serve.span"
    rid: int
    outcome: str            # eos | max_new | guard
    enqueue_tick: int
    admit_tick: int
    first_token_tick: int   # -1: never produced a token
    finish_tick: int
    prefill_chunks: int
    n_tokens: int
    ttft_s: float           # admit -> first token wall time; -1: no token
    wall_s: float           # admit -> finish wall time
    queue_s: float          # arrival -> admit wall time


@dataclasses.dataclass(frozen=True)
class SweepRound:
    """Population-sweep scheduler event (search/scheduler.py):
    ``action`` ∈ rank (one per round, scores in ``detail``) | prune |
    quarantine | winner (one per affected member, its cohort/slot
    attached so the sweep ledger and the telemetry share one
    timeline)."""
    KIND: ClassVar[str] = "sweep.round"
    action: str
    round: int
    member: int = -1
    cohort: int = -1
    slot: int = -1
    detail: dict = dataclasses.field(default_factory=dict)


EVENT_TYPES = (TrainStep, Guardian, Checkpoint, RequestSpan, SweepRound)


# --------------------------------------------------------------- histogram
class Histogram:
    """Bounded sample window: the newest ``cap`` observations (deque) plus
    lifetime count/sum, so percentiles cover the recent window while the
    mean stays exact over the whole run."""

    __slots__ = ("samples", "count", "total")

    def __init__(self, cap: int = 65536):
        self.samples: deque = deque(maxlen=cap)
        self.count = 0
        self.total = 0.0

    def observe(self, v: float) -> None:
        v = float(v)
        self.samples.append(v)
        self.count += 1
        self.total += v

    def percentile(self, q: float) -> float:
        return percentile(self.samples, q)

    def summary(self) -> dict:
        if not self.samples:
            return {"count": 0}
        return {
            "count": self.count,
            "mean": self.total / self.count,
            "min": min(self.samples),
            "max": max(self.samples),
            "p50": self.percentile(50),
            "p90": self.percentile(90),
            "p99": self.percentile(99),
        }


# ---------------------------------------------------------------- recorder
class Recorder:
    """The flight recorder.  Construct with ``path=`` for a JSONL sink
    (or ``None`` for in-memory only), hand it to the producers
    (``train_loop.run(recorder=)``, ``ContinuousEngine(recorder=)``,
    ``run_sweep(recorder=)``), and ``close()`` — or use it as a context
    manager — when the run ends.  Multiple producers may share one
    recorder: a sweep's round events and its cohorts' telemetry land on
    one timeline, ordered by ``seq``."""

    def __init__(self, path: str | None = None, *, ring: int = 4096,
                 meta: dict | None = None, hist_cap: int = 65536):
        self.counters: dict[str, int] = {}
        self.gauges: dict[str, float] = {}
        self.hists: dict[str, Histogram] = {}
        self.ring: deque = deque(maxlen=ring)
        self.n_events = 0
        self._hist_cap = hist_cap
        self._t0 = time.time()
        self._sink: Optional[IO[str]] = None
        if path is not None:
            self._sink = open(path, "w")
            self._write_frame("meta", dict(meta or {}, t0=self._t0))

    # -- aggregates
    def count(self, name: str, n: int = 1) -> None:
        _ensure_host(name, n)
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def gauge(self, name: str, value: float) -> None:
        _ensure_host(name, value)
        self.gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        _ensure_host(name, value)
        h = self.hists.get(name)
        if h is None:
            h = self.hists[name] = Histogram(self._hist_cap)
        h.observe(value)

    # -- events
    def emit(self, event) -> None:
        """Record one typed event (an EVENT_TYPES dataclass instance):
        append to the ring, stream to the JSONL sink."""
        if not isinstance(event, EVENT_TYPES):
            raise TypeError(f"emit() takes a telemetry event dataclass, "
                            f"got {type(event).__name__}")
        fields = dataclasses.asdict(event)
        for k, v in fields.items():
            _ensure_host(f"{event.KIND}.{k}", v)
        self.ring.append(event)
        if self._sink is not None:
            self._write_frame(event.KIND, fields)
        else:
            self.n_events += 1

    def events(self, kind: str | None = None) -> list:
        """Ring contents (newest-``ring`` events), optionally filtered."""
        return [e for e in self.ring if kind is None or e.KIND == kind]

    # -- lifecycle
    def summary(self) -> dict:
        return {
            "n_events": self.n_events,
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
            "histograms": {k: h.summary() for k, h in self.hists.items()},
        }

    def close(self) -> None:
        if self._sink is not None:
            self._write_frame("summary", self.summary())
            self._sink.close()
            self._sink = None

    def _write_frame(self, kind: str, fields: dict) -> None:
        rec = {"kind": kind, "ts": time.time(), "seq": self.n_events}
        rec.update(fields)
        self.n_events += 1
        self._sink.write(json.dumps(rec) + "\n")

    def __enter__(self) -> "Recorder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # best-effort: never leak an unsummarized sink
        try:
            self.close()
        except Exception:
            pass


def read_events(path: str) -> tuple[dict, list[dict]]:
    """(meta, events) from a JSONL sink file.  ``meta`` is the header
    frame's fields ({} for a truncated file); ``events`` every non-frame
    line as a dict, in ``seq`` order.  The trailing ``summary`` frame, if
    the recorder was closed cleanly, is returned as the last event with
    ``kind == "summary"`` so reports can cross-check their own
    aggregation against the recorder's."""
    meta: dict = {}
    events: list[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if rec.get("kind") == "meta":
                meta = {k: v for k, v in rec.items()
                        if k not in ("kind", "ts", "seq")}
            else:
                events.append(rec)
    return meta, events
