"""The chip's idle time put down to the program's spans, on hand-made
traces and on the trace recorded on a TPU v5 lite (no program spans)."""
from __future__ import annotations

import dataclasses
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import program_trace as pt
from chipbench import trace

DATA = Path(__file__).parent / "data" / "tiny_population_step.xplane.pb"


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _profile(ops, spans):
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)]),
        NS(name="/host:CPU", lines=[NS(name="python", events=spans)])])


# busy [0, 100] and [1000, 1100]: one idle gap, [100, 1000]
OPS = [_ev("%junction_fwd.1 = x", 0, 100), _ev("%fusion.2 = x", 1000, 100)]


def test_gap_across_sibling_spans_is_split():
    s = pt.summarize(_profile(OPS, [_ev("repro.sweep.setup", 50, 450),
                                    _ev("repro.sweep.first_step", 500, 550)]))
    assert s.window_s == pytest.approx(1100e-9)
    assert s.idle["repro.sweep.setup"] == pytest.approx(400e-9)
    assert s.idle["repro.sweep.first_step"] == pytest.approx(500e-9)
    assert pt.NO_SPAN not in s.idle


def test_nested_span_wins_over_its_parent():
    s = pt.summarize(_profile(OPS, [_ev("repro.serve.decode", 0, 1100),
                                    _ev("repro.serve.fetch", 300, 300)]))
    assert s.idle["repro.serve.fetch"] == pytest.approx(300e-9)
    assert s.idle["repro.serve.decode"] == pytest.approx(600e-9)
    assert s.spans["repro.serve.fetch"] == [1, pytest.approx(300e-9)]


def test_harness_spans_label_nothing():
    spans = [_ev("chipbench.window", 0, 1100), _ev("chipbench.sweep", 0, 1100),
             _ev("repro.sweep.step", 100, 200)]
    s = pt.summarize(_profile(OPS, spans))
    assert s.idle == {"repro.sweep.step": pytest.approx(200e-9),
                      pt.NO_SPAN: pytest.approx(700e-9)}
    assert set(s.spans) == {"repro.sweep.step"}


def test_spans_clipped_to_the_window():
    spans = [_ev("chipbench.window", 100, 800),
             _ev("repro.sweep.step", 0, 300),      # starts before it
             _ev("repro.sweep.step", 600, 900),    # ends after it
             _ev("repro.sweep.eval", 950, 100)]    # outside it
    s = pt.summarize(_profile(OPS, spans))
    assert s.window_s == pytest.approx(800e-9)
    assert s.spans == {"repro.sweep.step": [2, pytest.approx(500e-9)]}
    assert s.idle == {"repro.sweep.step": pytest.approx(500e-9),
                      pt.NO_SPAN: pytest.approx(300e-9)}
    assert sum(s.idle.values()) == pytest.approx(s.window_s)


def test_idle_under_is_none_without_its_spans():
    s = pt.summarize(_profile(OPS, [_ev("repro.serve.admit", 100, 400)]))
    assert s.idle_under(["repro.sweep.setup", "repro.sweep.first_step"]) \
        is None
    assert s.idle_under(["repro.serve.admit", "repro.serve.decode"]) == \
        pytest.approx(400e-9)


def test_program_spans_leave_the_harness_reduction_as_it_was():
    spans = [_ev("chipbench.window", 0, 1100), _ev("chipbench.sync", 50, 600)]
    program = [_ev("repro.serve.decode", 0, 1100),
               _ev("repro.serve.fetch", 300, 300)]
    without = trace.summarize(_profile(OPS, spans))
    with_ = trace.summarize(_profile(OPS, spans + program))
    assert dataclasses.asdict(with_) == dataclasses.asdict(without)
    assert with_.breakdown() == without.breakdown()


def test_recorded_trace_has_no_program_span():
    s = trace.load(str(DATA))
    p = pt.load(str(DATA))
    assert p.spans == {}
    assert p.window_s == pytest.approx(s.window_s)
    assert p.idle == {pt.NO_SPAN: pytest.approx(s.window_s - s.busy_s)}
    assert p.idle_under(["repro.sweep.step"]) is None
