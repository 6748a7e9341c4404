"""The trace reduction, on a trace recorded on a TPU v5 lite (four fused
population steps of E=2 members, 256 -> 256 -> 128, each followed by a
20 ms host wait inside the span ``chipbench.host_wait``) and on
hand-made traces."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data" / "tiny_population_step.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(DATA))


def test_op_names_strip_hlo_text():
    assert trace.op_name("%junction_fwd.2 = f32[2,128,256]{2,1,0} "
                         "custom-call(s32[2,1] %copy.7)") == "junction_fwd"
    assert trace.op_name("%copy-start.11 = (f32[2]) copy-start()") == \
        "copy-start"
    assert trace.op_name("%fusion = f32[] fusion()") == "fusion"


def test_recorded_trace_kernels(recorded):
    # per step: two junction_fwd, one junction_dx (the first junction
    # reads the data, so no input gradient), two junction_update_dw
    assert recorded.chips == 1
    assert recorded.op_count["junction_fwd"] == 8
    assert recorded.op_count["junction_dx"] == 4
    assert recorded.op_count["junction_update_dw"] == 8
    assert 0 < recorded.kernel_s("junction_") < recorded.busy_s


def test_recorded_trace_busy_and_gaps(recorded):
    assert 0.06 < recorded.window_s < 0.07
    assert 0 < recorded.busy_s < 1e-3
    assert recorded.idle_share > 0.99
    label, secs = recorded.gaps[0]
    assert label == "chipbench.host_wait"
    assert secs > 0.9 * (recorded.window_s - recorded.busy_s)
    assert recorded.spans["chipbench.step"][0] == 4
    bd = recorded.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "junction_update_dw"
    assert set(recorded.module_s) == {"jit_step"}
    assert recorded.busy_s <= recorded.module_s["jit_step"] < 2e-4


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _profile(ops, spans, chips=1):
    planes = [NS(name=f"/device:TPU:{c}",
                 lines=[NS(name="XLA Ops", events=ops),
                        NS(name="XLA Modules", events=[
                            _ev("jit_step(123)", 900, 2300)]),
                        NS(name="Steps", events=[_ev("0", 0, 10**9)])])
              for c in range(chips)]
    planes.append(NS(name="/host:CPU", lines=[NS(name="python",
                                                 events=spans)]))
    return NS(planes=planes)


def test_window_span_clips_and_labels_gaps():
    ops = [_ev("%junction_fwd.1 = x", 100, 50),     # before the window
           _ev("%junction_fwd.1 = x", 1000, 200),
           _ev("%fusion.3 = x", 1100, 300),          # overlaps the first
           _ev("%junction_dx.2 = x", 2000, 500),
           _ev("%copy.1 = x", 2900, 200)]            # runs past the end
    spans = [_ev("chipbench.window", 1000, 2000),
             _ev("chipbench.step", 900, 2200),
             _ev("chipbench.sync", 1500, 400),
             _ev("not.ours", 0, 5000)]
    s = trace.summarize(_profile(ops, spans, chips=2))
    assert s.chips == 2
    assert s.window_s == pytest.approx(2000e-9)
    # busy: [1000, 1400] + [2000, 2500] + [2900, 3000]
    assert s.busy_s == pytest.approx(1000e-9)
    assert s.idle_share == pytest.approx(0.5)
    assert s.op_s["junction_fwd"] == pytest.approx(2 * 200e-9)
    assert s.kernel_s("junction_") == pytest.approx(2 * 700e-9)
    gaps = dict(s.gaps)
    assert gaps["chipbench.sync"] == pytest.approx(600e-9)   # 1400-2000
    assert gaps["chipbench.step"] == pytest.approx(400e-9)   # 2500-2900
    assert s.module_s["jit_step"] == pytest.approx(2 * 2000e-9)


def test_enclosing_ops_keep_only_their_own_time():
    ops = [_ev("%while.1 = x", 0, 1000),
           _ev("%fusion.2 = x", 100, 300),
           _ev("%junction_fwd.3 = x", 500, 400),
           _ev("%copy.4 = x", 1200, 100)]
    s = trace.summarize(_profile(ops, []))
    assert s.op_s["while"] == pytest.approx(300e-9)
    assert s.op_s["junction_fwd"] == pytest.approx(400e-9)
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize(NS(planes=[NS(name="/host:CPU", lines=[])]))
