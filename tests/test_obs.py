"""Flight-recorder telemetry (ISSUE 10): the obs subsystem end to end.

Layers under test, bottom-up:
  * obs/telemetry — nearest-rank percentile (p99 of <100 samples is the
    max, never an interpolation past it), histogram/recorder mechanics,
    JSONL round-trip, and the no-extra-device-sync guard (recording a
    live jax.Array is a TypeError);
  * repro/artifacts — the one meta stamp round-trips through BOTH
    consumer schemas (BENCH via benchmarks.run.load_artifact, stamped
    and legacy flat, and the sweep Ledger);
  * train/train_loop — a poisoned run emits trip → rollback → backoff →
    recovery in order, step ids matching the loop's own guardian state,
    plus checkpoint save/promote events;
  * search/scheduler — a quarantined member's event carries its
    cohort/slot, matching the ledger record;
  * serve/engine — every completed request reconstructs a full span
    (validated by launch/obs_report.check_span) and the compile-once
    contract holds with the recorder attached (decode_traces ==
    prefill_traces == 1);
  * no-retrace regression — the jaxpr of the fused train step is
    IDENTICAL with and without a recorder attached to the loop;
  * obs.span — the scheduler's and the engine's spans in a CPU profiler
    trace: every name, nested as documented, on the Recorder's clock, with
    no retrace and no change to the fused population step's jaxpr; the
    sweep's per-call trace counts.
"""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import artifacts
from repro.obs import (Guardian, Histogram, NOT_SAMPLED, Recorder,
                       RequestSpan, SweepRound, TrainStep, percentile,
                       read_events, span)

# shared e2e fixtures: the guardian's poisoned-stream regression setup
from test_guardian import (PoisonPipeline, _junction, _make_regression_step,
                           _w_true)

from repro.configs.base import ArchConfig, SweepConfig
from repro.core.sparsity import SparsityConfig
from repro.launch.obs_report import build_report, check_span
from repro.models import model as M
from repro.search import CandidateSpec, run_sweep
from repro.serve.engine import ContinuousEngine, Request, ServeConfig
from repro.train.train_loop import GuardianConfig, TrainLoopConfig, run


# ------------------------------------------------------- percentile helper
def test_percentile_single_sample():
    """n=1: every percentile is that sample (the ISSUE's 1-sample case)."""
    for q in (1, 50, 99, 100):
        assert percentile([7.25], q) == 7.25


def test_percentile_two_samples():
    """n=2: p50 is the smaller (rank ceil(0.5*2)=1), p99/p100 the max —
    NOT a value interpolated past the larger observation (np.percentile's
    linear default returns 1.98 for p99 of [1, 2])."""
    assert percentile([2.0, 1.0], 50) == 1.0
    assert percentile([2.0, 1.0], 99) == 2.0
    assert percentile([2.0, 1.0], 100) == 2.0


def test_percentile_hundred_samples():
    """n=100: nearest rank lands on exact order statistics."""
    xs = list(range(1, 101))            # 1..100
    assert percentile(xs, 1) == 1
    assert percentile(xs, 50) == 50
    assert percentile(xs, 99) == 99
    assert percentile(xs, 100) == 100


def test_percentile_small_sample_p99_is_max():
    """p99 of any <100-sample set is the worst OBSERVED value."""
    for n in (1, 2, 5, 50, 99):
        xs = np.random.default_rng(n).standard_normal(n).tolist()
        assert percentile(xs, 99) == max(xs)


def test_percentile_rejects_bad_input():
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 0)
    with pytest.raises(ValueError):
        percentile([1.0], 101)


# ------------------------------------------------------- recorder mechanics
def test_histogram_summary_and_window():
    h = Histogram(cap=4)
    for v in (5.0, 1.0, 2.0, 3.0, 4.0):     # 5.0 evicted by the window
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5                  # lifetime count
    assert s["mean"] == pytest.approx(3.0)  # lifetime mean
    assert s["min"] == 1.0 and s["max"] == 4.0  # windowed extrema
    assert h.percentile(99) == 4.0


def test_recorder_ring_and_jsonl_round_trip(tmp_path):
    p = str(tmp_path / "obs.jsonl")
    with Recorder(p, ring=3, meta={"launcher": "test", "tag": "t"}) as r:
        r.count("steps", 2)
        r.count("steps")
        r.gauge("lr", 0.5)
        r.observe("dt", 0.25)
        for i in range(5):
            r.emit(TrainStep(step=i, loss=float(i), nonfinite=NOT_SAMPLED,
                             lr_scale=1.0, dt_s=0.1, dt_ema_s=0.1,
                             tokens_per_s=10.0))
    assert r.counters["steps"] == 3
    # ring keeps only the newest 3 events; the sink keeps all 5
    assert [e.step for e in r.events("train.step")] == [2, 3, 4]
    meta, events = read_events(p)
    assert meta["launcher"] == "test" and meta["tag"] == "t"
    steps = [e for e in events if e["kind"] == "train.step"]
    assert [e["step"] for e in steps] == [0, 1, 2, 3, 4]
    assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)
    # close() appended the summary frame with the aggregates
    assert events[-1]["kind"] == "summary"
    assert events[-1]["counters"]["steps"] == 3
    assert events[-1]["histograms"]["dt"]["count"] == 1


def test_recorder_emit_rejects_untyped_events():
    with pytest.raises(TypeError):
        Recorder().emit({"kind": "train.step"})


def test_recorder_rejects_device_arrays():
    """The no-extra-device-sync contract is enforced, not advisory:
    recording a live jax.Array (which would force a D2H transfer) raises
    instead of silently syncing."""
    r = Recorder()
    dev = jnp.float32(1.5)
    with pytest.raises(TypeError, match="no-extra-device-sync"):
        r.gauge("lr", dev)
    with pytest.raises(TypeError, match="no-extra-device-sync"):
        r.observe("dt", dev)
    with pytest.raises(TypeError, match="no-extra-device-sync"):
        r.emit(TrainStep(step=0, loss=dev, nonfinite=0.0, lr_scale=1.0,
                         dt_s=0.1, dt_ema_s=0.1, tokens_per_s=1.0))
    r.gauge("lr", float(dev))               # host float: fine


# -------------------------------------------------- artifact meta stamping
def test_artifact_meta_round_trips_bench_schemas(tmp_path):
    """The one repro.artifacts stamp survives both BENCH_*.json schemas:
    the stamped {"meta", "results"} form round-trips meta exactly, the
    legacy flat form loads with empty meta."""
    from benchmarks.run import load_artifact

    meta = artifacts.artifact_meta("pr10")
    assert set(meta) == {"git_sha", "backend", "jax_version", "tag",
                         "timestamp"}
    assert meta["tag"] == "pr10"

    stamped = tmp_path / "BENCH_stamped.json"
    stamped.write_text(json.dumps(
        {"meta": meta, "results": {"bench.x": 1.5}}))
    got_meta, got_results = load_artifact(str(stamped))
    assert got_meta == meta
    assert got_results == {"bench.x": 1.5}

    legacy = tmp_path / "BENCH_legacy.json"
    legacy.write_text(json.dumps({"bench.x": 2.5}))
    got_meta, got_results = load_artifact(str(legacy))
    assert got_meta == {}
    assert got_results == {"bench.x": 2.5}


def test_artifact_meta_round_trips_sweep_ledger(tmp_path):
    """The sweep Ledger writes the SAME stamp schema and round-trips it
    through save/load."""
    from repro.search.ledger import Ledger, MemberRecord, make_meta

    led = Ledger(meta=dict(make_meta("pr10-sweep"), rounds=2))
    led.add(MemberRecord(member=0, config={"lr": 0.1}, cohort=0, slot=0))
    p = str(tmp_path / "SWEEP_t.json")
    led.save(p)
    back = Ledger.load(p)
    assert back.meta == led.meta
    assert set(back.meta) >= {"git_sha", "backend", "jax_version", "tag",
                              "timestamp"}
    assert back.meta["tag"] == "pr10-sweep"
    assert back.members[0].member == 0 and back.members[0].slot == 0


# ---------------------------------------------------- guardian event stream
def test_guardian_event_stream_matches_loop_state(tmp_path):
    """A poisoned-batch run emits trip → rollback → backoff → recovery in
    order, with step ids matching the train loop's own guardian state
    (the same scenario as test_guardian_rollback_recovers_poisoned_run:
    poison at data step 12, ckpt_every=5 → trip at 12, rollback to 5)."""
    w_true = _w_true()
    params = _junction()
    opt, train_step = _make_regression_step("jnp")
    total, poison_at = 30, 12
    g = GuardianConfig(health_window=5, lr_backoff=0.5, max_retries=3,
                       min_history=4)
    rec = Recorder(str(tmp_path / "obs.jsonl"))
    res = run(TrainLoopConfig(total, str(tmp_path / "ck"), ckpt_every=5,
                              log_every=5, guardian=g),
              train_step, params, opt.init(params),
              PoisonPipeline(w_true, frozenset([poison_at])),
              log=lambda s: None, recorder=rec)
    rec.close()

    assert res["step"] == total
    trips = res["guardian"]["trips"]
    assert len(trips) == 1

    gev = rec.events("guardian")
    assert [e.action for e in gev] == ["trip", "rollback", "backoff",
                                      "recovery"]
    trip, rollback, backoff, recovery = gev
    # trip carries the discarded step + the loop's own trip record fields
    assert trip.step == trips[0]["step"] == poison_at
    assert trip.detail["data_step"] == poison_at
    assert trip.detail["reason"] == trips[0]["reason"]
    # rollback landed on the latest HEALTHY checkpoint: step 5 (the step-10
    # checkpoint existed but hadn't survived its health window at trip time)
    assert rollback.step == 5
    assert rollback.detail["from_step"] == poison_at
    # backoff halved the lr; recovery is the first adopted step after
    assert backoff.detail["lr_scale"] == res["guardian"]["lr_scale"] == 0.5
    assert recovery.step == rollback.step
    assert recovery.detail["lr_scale"] == 0.5

    # events are causally ordered around the trip in the one timeline
    meta, events = read_events(str(tmp_path / "obs.jsonl"))
    kinds = [(e["kind"], e.get("action")) for e in events]
    i_trip = kinds.index(("guardian", "trip"))
    i_rec = kinds.index(("guardian", "recovery"))
    assert i_trip < i_rec
    # the step before the trip was adopted at the pre-rollback step id;
    # the first step after recovery resumes from the rollback target
    pre = [e for e in events[:i_trip] if e["kind"] == "train.step"]
    post = [e for e in events[i_rec:] if e["kind"] == "train.step"]
    assert pre[-1]["step"] == poison_at - 1
    assert post[0]["step"] == rollback.step
    assert all(e["lr_scale"] == 0.5 for e in post)
    # per-step records carry the guardian-path nonfinite (0 on clean
    # steps, never the NOT_SAMPLED sentinel when the guardian is on)
    assert all(e["nonfinite"] == 0.0 for e in pre + post)

    # checkpoint lifecycle rode the same stream: saves at ckpt_every and
    # promotions only for checkpoints that survived the health window
    saves = [e["step"] for e in events
             if e["kind"] == "checkpoint" and e["action"] == "save"]
    promotes = [e["step"] for e in events
                if e["kind"] == "checkpoint" and e["action"] == "promote"]
    assert 5 in saves and 10 in saves and total in saves
    assert promotes == sorted(promotes) and len(promotes) >= 1
    assert all(s in saves for s in promotes)


def test_train_steps_without_guardian_use_sentinel(tmp_path):
    """Guardian off: the loop never fetched metrics['nonfinite'], so the
    per-step record carries NOT_SAMPLED rather than forcing a D2H
    transfer the step didn't already pay for."""
    params = _junction()
    opt, train_step = _make_regression_step("jnp")
    rec = Recorder()
    run(TrainLoopConfig(6, str(tmp_path / "ck"), ckpt_every=50),
        train_step, params, opt.init(params), PoisonPipeline(_w_true()),
        log=lambda s: None, recorder=rec)
    steps = rec.events("train.step")
    assert len(steps) == 6
    assert all(e.nonfinite == NOT_SAMPLED for e in steps)
    assert all(e.tokens_per_s > 0 for e in steps)


# ------------------------------------------------- sweep quarantine events
def test_sweep_quarantine_event_carries_cohort_slot():
    """A quarantined member's event carries its cohort/slot, matching the
    ledger record — sweep telemetry and ledger share one timeline."""
    N_IN, N_OUT = 128, 64
    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, N_IN)).astype(np.float32)
    t = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, 256)]
    xe = rng.standard_normal((64, N_IN)).astype(np.float32)
    te = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, 64)]

    def spec(lr, i):
        return CandidateSpec(lr=lr, momentum=0.0, density=0.5,
                             layers=(N_IN, N_OUT), block=32, init_seed=i)

    rec = Recorder()
    result = run_sweep([spec(0.05, 0), spec(0.1, 1), spec(float("inf"), 2)],
                       x, t, xe, te,
                       SweepConfig(rounds=2, steps_per_round=4,
                                   batch_size=32, eval_samples=64,
                                   keep_fraction=1.0, engine="jnp",
                                   fused=False),
                       recorder=rec)
    qrec = result.ledger.members[2]
    assert qrec.quarantined_at is not None

    qev = [e for e in rec.events("sweep.round") if e.action == "quarantine"]
    assert len(qev) == 1
    assert qev[0].member == qrec.member == 2
    assert qev[0].cohort == qrec.cohort
    assert qev[0].slot == qrec.slot
    assert qev[0].round == qrec.quarantined_at["round"]
    assert qev[0].detail["step"] == qrec.quarantined_at["step"]

    # every round ranked; the winner event names the ledger's winner
    ranks = [e for e in rec.events("sweep.round") if e.action == "rank"]
    assert [e.round for e in ranks] == [0, 1]
    assert ranks[0].detail["live"] == 2     # quarantined before 1st eval
    winner = [e for e in rec.events("sweep.round") if e.action == "winner"]
    assert len(winner) == 1
    assert winner[0].member == result.ledger.winner().member


# ------------------------------------------------------ serve request spans
def _serve_cfg(engine="jnp"):
    return ArchConfig(
        name="obs-serve", family="dense", n_layers=2, d_model=128,
        n_heads=4, kv_heads=2, head_dim=32, d_ff=256, vocab=128,
        act="silu", max_seq=64, attn_chunk=32, dtype="float32",
        sparsity=SparsityConfig(density=0.25, block=32, where="ffn"),
        engine=engine)


def test_serve_spans_full_lifecycle_compile_once(tmp_path):
    """Every completed request reconstructs a full span (enqueue ≤ admit
    ≤ first token ≤ finish, chunks and tokens counted) AND the engine
    still compiles each step exactly once with the recorder attached —
    the no-retrace half of the no-extra-device-sync contract."""
    cfg = _serve_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab, size=(5, 12)).astype(np.int32)
    NEW = 8
    p = str(tmp_path / "serve.jsonl")
    rec = Recorder(p)
    ce = ContinuousEngine(
        cfg, params,
        ServeConfig(max_new_tokens=NEW, eos_token=-1, slots=2, page_size=8,
                    prefill_chunk=8, max_seq=32),
        recorder=rec)
    outs = ce.serve([Request(rid=i, prompt=prompts[i], max_new_tokens=NEW,
                             arrival=2 * i)
                     for i in range(len(prompts))])
    rec.close()

    st = ce.stats
    assert st["decode_traces"] == 1 and st["prefill_traces"] == 1
    assert set(outs) == set(range(5))

    spans = rec.events("serve.span")
    assert sorted(s.rid for s in spans) == list(range(5))
    for s in spans:
        assert s.outcome == "max_new"
        assert (s.enqueue_tick <= s.admit_tick <= s.first_token_tick
                <= s.finish_tick)
    # spans validate through the SAME checker the CI smoke gate uses
    meta, events = read_events(p)
    ev_spans = [e for e in events if e["kind"] == "serve.span"]
    assert len(ev_spans) == 5
    for e in ev_spans:
        assert check_span(e) is None, check_span(e)
        assert e["n_tokens"] == NEW
        assert e["prefill_chunks"] >= 2     # 12-token prompt, 8-wide chunks
        assert e["ttft_s"] >= 0

    # latency dict mirrors the span fields (stats consumers see one truth)
    for rid, v in st["latency"].items():
        assert v["outcome"] == "max_new"
        assert v["n_tokens"] == NEW and v["ttft_s"] >= 0

    # histograms: one ttft per request; itl for the later tokens
    assert rec.hists["serve.ttft_s"].count == 5
    assert rec.hists["serve.itl_s"].count == 5 * (NEW - 1)
    # occupancy counters, hand-counted: each request's first token comes
    # off its last prefill chunk and the other NEW-1 from decode ticks,
    # one slot-tick each; two 3-page requests (20 tokens, pages of 8) run
    # at once on 2 slots
    assert st["decode_slot_ticks"] == 5 * (NEW - 1)
    assert st["peak_pages"] == 2 * 3
    assert rec.counters["serve.finish.max_new"] == 5
    # queue wait: request 2 arrives at tick 4 with both slots busy until
    # request 0 finishes, so it waits; nobody waits a negative time
    assert all(v["queue_s"] >= 0 for v in st["latency"].values())
    assert st["latency"][2]["admitted"] > 4
    assert st["latency"][2]["queue_s"] > 0

    # the report builder renders the run and agrees with the checker
    report = build_report(events)
    assert report["serve"]["requests"] == 5
    assert report["serve"]["outcomes"] == {"max_new": 5}
    assert report["serve"]["ttft_p99_s"] is not None
    assert (report["serve"]["ttft_arrival_p99_s"]
            >= report["serve"]["ttft_p99_s"])


def test_serve_guard_span_outcome(tmp_path):
    """A guard-terminated request's span carries outcome='guard' and is
    still a valid lifecycle (first token may be missing)."""
    cfg = _serve_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    # poison the final-norm scale so every logit row goes non-finite
    params = jax.tree_util.tree_map_with_path(
        lambda kp, x: (jnp.full_like(x, jnp.nan)
                       if "final" in jax.tree_util.keystr(kp) else x),
        params)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, cfg.vocab, size=(2, 12)).astype(np.int32)
    rec = Recorder()
    ce = ContinuousEngine(
        cfg, params,
        ServeConfig(max_new_tokens=4, eos_token=-1, slots=2, page_size=8,
                    prefill_chunk=8, max_seq=32),
        recorder=rec)
    ce.serve([Request(rid=i, prompt=prompts[i], max_new_tokens=4)
              for i in range(2)])
    spans = rec.events("serve.span")
    assert len(spans) == 2
    for s in spans:
        assert s.outcome == "guard"
        assert s.first_token_tick == -1 and s.ttft_s == -1.0
        d = {f: getattr(s, f) for f in s.__dataclass_fields__}
        d["kind"] = s.KIND
        assert check_span(d) is None
    assert rec.counters["serve.finish.guard"] == 2
    assert ce.nonfinite_terminated == 2


# ------------------------------------------------------ no-retrace contract
def test_fused_train_step_jaxpr_unchanged_by_recorder(tmp_path):
    """The acceptance gate: the jaxpr of the (fused-capable) train step
    is IDENTICAL whether or not a recorder is attached to the loop — the
    recorder adds no traced ops, no new operands, no retraces."""
    params = _junction()
    opt, train_step = _make_regression_step("pallas")
    batch = jax.tree.map(jnp.asarray, next(PoisonPipeline(_w_true())))
    args = (params, opt.init(params), batch, jnp.asarray(0),
            jnp.float32(1.0))
    jaxpr_before = str(jax.make_jaxpr(train_step)(*args))

    rec = Recorder(str(tmp_path / "obs.jsonl"))
    run(TrainLoopConfig(4, str(tmp_path / "ck"), ckpt_every=50,
                        guardian=GuardianConfig()),
        train_step, params, opt.init(params), PoisonPipeline(_w_true()),
        log=lambda s: None, recorder=rec)
    rec.close()
    assert len(rec.events("train.step")) == 4

    jaxpr_after = str(jax.make_jaxpr(train_step)(*args))
    assert jaxpr_after == jaxpr_before


# ------------------------------------------------------------ program spans
def _trace_spans(trace_dir):
    """(profile start on the wall clock in ns, [(name, start_ns, end_ns)]
    of every repro.* span) from the trace jax.profiler wrote."""
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    prof = ProfileData.from_file(path)
    start = next(v for p in prof.planes if p.name == "Task Environment"
                 for k, v in p.stats if k == "profile_start_time")
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for p in prof.planes if p.name.startswith("/host:")
             for line in p.lines for ev in line.events
             if ev.name.startswith("repro.")]
    return start, spans


def _inside(inner, outers):
    return any(s <= inner[1] and inner[2] <= e for _, s, e in outers)


def _by_name(spans):
    out = {}
    for sp in spans:
        out.setdefault(sp[0], []).append(sp)
    return out


def test_span_times_into_recorder_and_adds_nothing_else():
    """A span with a recorder observes its duration into span.<name>_s;
    without one it records nothing; neither touches jax state."""
    rec = Recorder()
    with span("unit.work", rec):
        pass
    with span("unit.work"):
        pass
    h = rec.hists["span.unit.work_s"]
    assert h.count == 1 and h.total >= 0
    assert rec.counters == {} and rec.gauges == {} and rec.n_events == 0


def _sweep_specs(n_in=128, n_out=64):
    return [CandidateSpec(lr=lr, momentum=0.0, density=d,
                          layers=(n_in, n_out), block=32, init_seed=i)
            for i, (d, lr) in enumerate((d, lr) for d in (0.5, 0.25)
                                        for lr in (0.05, 0.1))]


def _sweep_data(n_in=128, n_out=64):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((128, n_in)).astype(np.float32)
    t = np.eye(n_out, dtype=np.float32)[rng.integers(0, n_out, 128)]
    return x, t, x[:32], t[:32]


def test_sweep_spans_in_profiler_trace(tmp_path, cold_sweep_programs):
    """Every sweep span, nested as documented, with the counts the
    schedule implies (2 cohorts, 2 rounds of 3 steps, nobody pruned),
    and one trace of each cohort's step and eval on a cold call."""
    cfg = SweepConfig(rounds=2, steps_per_round=3, batch_size=32,
                      eval_samples=32, keep_fraction=1.0, engine="jnp",
                      fused=False)
    rec = Recorder()
    with jax.profiler.trace(str(tmp_path)):
        res = run_sweep(_sweep_specs(), *_sweep_data(), cfg, recorder=rec)
    _, spans = _trace_spans(str(tmp_path))
    by = _by_name(spans)
    cohorts, steps = 2, cfg.rounds * cfg.steps_per_round
    assert {k: len(v) for k, v in by.items()} == {
        "repro.sweep.setup": 1,
        "repro.sweep.first_step": cohorts,
        "repro.sweep.step": cohorts * (steps - 1),
        "repro.sweep.fetch": cohorts * steps,
        "repro.sweep.eval": cohorts * cfg.rounds,
        "repro.sweep.prune": cfg.rounds}
    steps_ = by["repro.sweep.first_step"] + by["repro.sweep.step"]
    assert all(_inside(f, steps_) for f in by["repro.sweep.fetch"])
    setup_end = by["repro.sweep.setup"][0][2]
    assert all(s >= setup_end for n, s, _ in spans
               if n != "repro.sweep.setup")
    assert max(e for _, _, e in by["repro.sweep.first_step"]) <= min(
        s for _, s, _ in by["repro.sweep.step"])
    # top-level spans never overlap one another
    top = sorted((sp for sp in spans if sp[0] != "repro.sweep.fetch"),
                 key=lambda sp: sp[1])
    assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))
    assert res.ledger.meta["step_traces"] == cohorts
    assert res.ledger.meta["eval_traces"] == cohorts
    assert rec.hists["span.sweep.fetch_s"].count == cohorts * steps


def test_sweep_trace_counts_are_per_call(cold_sweep_programs):
    """Each call's ledger counts its own traces: one per cohort and kind
    on a cold call, none on a second call of the same structures and
    shapes, whose cohorts all take their step and eval from the program
    cache (the cold call's second cohort already reuses the first's)."""
    cfg = SweepConfig(rounds=1, steps_per_round=2, batch_size=32,
                      eval_samples=32, engine="jnp", fused=False)
    rec = Recorder()
    metas = [run_sweep(_sweep_specs(), *_sweep_data(), cfg,
                       recorder=rec).ledger.meta for _ in range(2)]
    assert [(m["step_traces"], m["eval_traces"], m["programs_reused"])
            for m in metas] == [(2, 2, 1), (0, 0, 2)]
    assert rec.counters["sweep.programs_reused"] == 3


def test_population_step_jaxpr_unchanged_by_spans_and_trace_counter():
    """The fused population step's jaxpr is identical with and without
    a trace counter and inside or outside an open span: spans and
    counters add no traced op."""
    from repro.search import hyp_table, init_population, population as pop
    specs = _sweep_specs(n_in=64, n_out=64)[:2]
    params = init_population(jax.random.PRNGKey(0), specs)
    x, t, _, _ = _sweep_data(n_in=64, n_out=64)
    args = (params, pop.init_slots(params, specs), hyp_table(specs),
            jnp.ones((2,), jnp.float32), jnp.asarray(x[:16]),
            jnp.asarray(t[:16]))

    def jaxpr(traces):
        return str(jax.make_jaxpr(pop.make_population_step(
            engine="pallas", fused=True, jit=False, with_health=True,
            traces=traces))(*args))

    plain = jaxpr(None)
    traces = {}
    with span("sweep.step", Recorder()):
        counted = jaxpr(traces)
    assert counted == plain
    assert "junction_update_dw" in plain
    assert traces == {"step": 1}


def test_serve_spans_in_profiler_trace_one_clock(tmp_path):
    """Every serve span, nested as documented and counted as the stats
    say; each request's span event (emitted at finish, inside a decode
    tick) has a ts inside a repro.serve.decode span: one clock.  The
    engine still compiles each step once with spans and a recorder."""
    cfg = _serve_cfg()
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = np.random.default_rng(0).integers(
        1, cfg.vocab, size=(5, 12)).astype(np.int32)
    NEW = 8
    p = str(tmp_path / "serve.jsonl")
    rec = Recorder(p)
    ce = ContinuousEngine(
        cfg, params,
        ServeConfig(max_new_tokens=NEW, eos_token=-1, slots=2, page_size=8,
                    prefill_chunk=8, max_seq=32),
        recorder=rec)
    with jax.profiler.trace(str(tmp_path / "trace")):
        ce.serve([Request(rid=i, prompt=prompts[i], max_new_tokens=NEW,
                          arrival=2 * i) for i in range(5)])
    rec.close()
    st = ce.stats
    assert st["decode_traces"] == 1 and st["prefill_traces"] == 1

    start, spans = _trace_spans(str(tmp_path / "trace"))
    by = _by_name(spans)
    assert {k: len(v) for k, v in by.items()} == {
        "repro.serve.setup": 1,
        "repro.serve.admit": st["ticks"],
        "repro.serve.prefill": st["prefill_chunks"],
        "repro.serve.decode": st["decode_ticks"],
        "repro.serve.fetch": st["decode_ticks"] + 5}
    work = by["repro.serve.prefill"] + by["repro.serve.decode"]
    assert all(_inside(f, work) for f in by["repro.serve.fetch"])
    top = sorted((sp for sp in spans if sp[0] != "repro.serve.fetch"),
                 key=lambda sp: sp[1])
    assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))

    _, events = read_events(p)
    finished = [e for e in events if e["kind"] == "serve.span"]
    assert len(finished) == 5
    slack_ns = 1e3          # a float ts of ~1.8e9 s resolves ~0.24 us
    for e in finished:
        ts = e["ts"] * 1e9 - start
        assert any(s - slack_ns <= ts <= end + slack_ns
                   for _, s, end in by["repro.serve.decode"]), e["rid"]
        assert e["queue_s"] >= 0
    # the recorder timed every span too
    assert rec.hists["span.serve.admit_s"].count == st["ticks"]
    report = build_report(events)
    assert report["spans"]["serve.decode"]["count"] == st["decode_ticks"]


def test_check_span_rejects_negative_queue_wait():
    ev = {"kind": "serve.span", "rid": 3, "outcome": "max_new",
          "enqueue_tick": 0, "admit_tick": 1, "first_token_tick": 2,
          "finish_tick": 4, "prefill_chunks": 1, "n_tokens": 3,
          "ttft_s": 0.01, "wall_s": 0.02, "queue_s": 0.005}
    assert check_span(ev) is None
    assert "queue" in check_span(dict(ev, queue_s=-0.001))
