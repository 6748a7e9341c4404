"""Successive-halving scheduler over E-batched population cohorts.

``run_sweep`` takes an arbitrary candidate list, buckets it into
same-structure cohorts (search/cohorts.py), stacks each cohort into one
population (search/population.py), and runs ``SweepConfig.rounds`` of

    train steps_per_round E-batched steps
      -> vectorized per-member eval loss on the held-out split
      -> rank ALL live members globally, keep the top keep_fraction,
         prune the rest

Cross-cohort ranking is width-normalized: cohorts can differ in output
width (zero-padded targets), and a per-element MSE mean would dilute
with padding — so members rank on the per-sample TOTAL squared error
(``loss * n_out``), and a non-finite eval loss (a diverged candidate)
ranks as +inf: diverged members are pruned first and can never be named
winner.

Pruning is in place and shape-stable: a pruned member's mask entry goes
to 0 (its loss drops out of the objective, so its gradients are exact
zeros) and its hyp row goes to all zeros (the kernels' guarded epilogue
makes an all-zero registry row an exact freeze for SGD and Adam alike:
w' = w, slots' = 0).  The arrays
the jitted step sees never change shape — the serve engine's
finished-slot masking applied to training, and the paper's "greater
exploration ... on-chip" claim as a subsystem: exploration cost scales
with rounds, not candidates.

The jitted step and eval are built once per structure per process
(``_programs``): weights, pattern leaves, slots, the hyp table, the mask
and the data are all traced operands, so one program serves every cohort
of the same activation, engine and update path, on every call, and JAX
keeps one executable per shape.  A cohort's step is traced by the first
call that meets its shapes and never again: a later call with the same
structures and shapes traces nothing.  Only a process that calls
``run_sweep`` more than once gains; a single call (``launch/sweep.py``
makes one) traces each cohort once, as before.  The programs, and an
executable for every shape the process has run, are kept until
``clear_program_cache`` drops them: a long-lived caller that sweeps many
widths or batch sizes holds them all.

The same mechanism doubles as FAULT ISOLATION (``SweepConfig.quarantine``,
on by default): exploring lr×density means routinely training members at
hyperparameters that diverge, and a diverged member's non-finite loss
would otherwise sit inside the cohort's shared-batch objective every
step.  After every train step the scheduler checks each live member's
loss and per-member health flag (population.make_population_step
``with_health`` — the fused path's in-kernel detector, since those
gradients never reach HBM) and quarantines diverged members MID-round:
mask + hyp zeroed immediately, the event recorded in the ledger
(``quarantined_at``).  Member independence makes this exact: survivors'
gradient trajectories are bitwise identical to a cohort that never
contained the diverged member (tests/test_guardian.py).

The returned ``SweepResult`` carries the lineage ``Ledger`` (winner,
loss curves, rounds survived) plus the live cohort states for callers
that want the winning weights.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SweepConfig
from repro.core.sparse_linear import resolve_engine
from repro.obs import telemetry as obs
from repro.search import cohorts as ch
from repro.search import population as pop
from repro.search.ledger import Ledger, MemberRecord, make_meta


@dataclasses.dataclass
class CohortState:
    cohort: ch.Cohort
    params: list
    mom: tuple              # accumulator-slot trees (population.init_slots)
    hyp: jax.Array          # [E, HYP_K], zeroed rows = pruned
    mask: jax.Array         # [E] f32, 0 = pruned
    records: list[MemberRecord]
    step: callable
    evaluate: callable
    t_train_pad: jax.Array  # train targets padded to this cohort's width
    t_eval_pad: jax.Array   # eval targets, ditto (constant per cohort)

    @property
    def out_width(self) -> int:
        return self.cohort.specs[0].layers[-1]

    @property
    def is_adam(self) -> bool:
        # homogeneous per cohort: opt is part of the structure key
        return self.cohort.specs[0].opt == "adam"


@dataclasses.dataclass
class SweepResult:
    ledger: Ledger
    states: list[CohortState]

    def winning_params(self):
        """The winner's standalone single-model params."""
        w = self.ledger.winner()
        if w is None:
            return None
        st = self.states[w.cohort]
        return pop.member_slice(st.params, w.slot)


def _pad_targets(t: np.ndarray, width: int) -> np.ndarray:
    """One-hot targets padded with zero columns to a cohort's output
    width (the paper pads 10 MNIST classes to its 32-wide output)."""
    if t.shape[1] > width:
        raise ValueError(f"targets wider ({t.shape[1]}) than the output "
                         f"layer ({width})")
    if t.shape[1] == width:
        return t
    out = np.zeros((t.shape[0], width), t.dtype)
    out[:, :t.shape[1]] = t
    return out


def _batch_indices(n: int, batch: int, step: int) -> np.ndarray:
    """Deterministic wrapping minibatch of the shared train split —
    every cohort sees the same data stream."""
    start = (step * batch) % n
    return (np.arange(start, start + batch) % n).astype(np.int64)


def _score(loss: float, out_width: int) -> float:
    """Cross-cohort comparable rank key: per-sample total squared error
    (mean * width undoes the padding dilution of wider outputs); any
    non-finite loss — a diverged candidate — ranks strictly last."""
    s = float(loss) * out_width
    return s if math.isfinite(s) else math.inf


def _quarantine(st: CohortState, rec: MemberRecord, rnd: int,
                global_step: int, recorder: "obs.Recorder | None" = None):
    """Fault-isolate a diverged member MID-round: zero its mask entry
    (its — possibly non-finite — loss drops out of the shared-batch
    objective, and member independence makes the surviving members'
    gradients exactly what they'd be without it) and its hyp row (lr =
    momentum = 0 freezes whatever parameter state remains).  The same
    in-place mechanism as round-boundary pruning, applied the moment the
    divergence is detected rather than at the next eval; recorded
    distinctly in the ledger."""
    st.mask = st.mask.at[rec.slot].set(0.0)
    st.hyp = st.hyp.at[rec.slot].set(0.0)
    rec.pruned_at = rnd
    rec.quarantined_at = {"round": rnd, "step": global_step}
    if recorder is not None:
        recorder.count("sweep.quarantined")
        recorder.emit(obs.SweepRound(
            action="quarantine", round=rnd, member=rec.member,
            cohort=rec.cohort, slot=rec.slot,
            detail={"step": global_step}))


class _ThreadTraces(threading.local):
    """Traces of the cached programs made by the calling thread, by kind
    (the ``traces`` dict protocol of the factories).  JAX traces a jitted
    function in the thread that calls it, so the delta a ``run_sweep``
    call records holds its own traces only, whatever other threads sweep
    meanwhile."""

    def __init__(self):
        self.counts = {"step": 0, "eval": 0}

    def get(self, kind, default=0):
        return self.counts.get(kind, default)

    def __setitem__(self, kind, n):
        self.counts[kind] = n


# (step factory, eval factory, act, engine, fused update, with_health) ->
# (jitted step, jitted eval).  Every cached program counts its traces in
# _TRACES; run_sweep records a call's delta.
_PROGRAMS: dict[tuple, tuple] = {}
_TRACES = _ThreadTraces()


def clear_program_cache() -> None:
    """Drop the cached cohort programs: the next ``run_sweep`` builds,
    and JAX traces, each cohort's step and eval anew."""
    _PROGRAMS.clear()


def _programs(act: str, cfg: SweepConfig):
    """((step, evaluate), reused) for a cohort of activation ``act``:
    the pair is built on first use and kept for the process.  The key is
    everything the traced functions close over, the two factories
    included (read from ``population`` at lookup, so a replaced factory
    gets programs of its own)."""
    make_step, make_eval = pop.make_population_step, pop.make_population_eval
    engine = resolve_engine(cfg.engine)
    # the factory fuses the update on the pallas engine only
    fused = cfg.fused and engine == "pallas"
    key = (make_step, make_eval, act, engine, fused, cfg.quarantine)
    progs = _PROGRAMS.get(key)
    if progs is not None:
        return progs, True
    progs = _PROGRAMS[key] = (
        make_step(act, engine=engine, fused=fused,
                  with_health=cfg.quarantine, traces=_TRACES),
        make_eval(act, engine=engine, traces=_TRACES))
    return progs, False


def _setup(specs, x_train, t_train, x_eval, t_eval, cfg: SweepConfig,
           tag: str):
    """The ledger, each cohort's state (weights, slots, hyp table, step
    and eval from the program cache, padded targets) and the data on the
    device."""
    x_train = np.asarray(x_train, np.float32)
    t_train = np.asarray(t_train, np.float32)
    x_eval = np.asarray(x_eval, np.float32)[:cfg.eval_samples]
    t_eval = np.asarray(t_eval, np.float32)[:cfg.eval_samples]

    ledger = Ledger(meta=dict(make_meta(tag), engine=cfg.engine,
                              rounds=cfg.rounds,
                              steps_per_round=cfg.steps_per_round,
                              n_candidates=len(specs)))
    key = jax.random.PRNGKey(cfg.seed)
    states: list[CohortState] = []
    reused = 0
    for ci, cohort in enumerate(ch.bucket(specs)):
        spec0 = cohort.specs[0]
        if x_train.shape[1] != spec0.layers[0]:
            raise ValueError(
                f"cohort {ci}: input width {spec0.layers[0]} != data "
                f"width {x_train.shape[1]}")
        params = pop.init_population(jax.random.fold_in(key, ci),
                                     cohort.specs)
        records = [ledger.add(MemberRecord(
            member=mid, config=s.to_dict(), cohort=ci, slot=slot))
            for slot, (mid, s) in enumerate(zip(cohort.member_ids,
                                                cohort.specs))]
        (step, evaluate), hit = _programs(spec0.act, cfg)
        reused += hit
        states.append(CohortState(
            cohort=cohort, params=params,
            mom=pop.init_slots(params, cohort.specs),
            hyp=pop.hyp_table(cohort.specs),
            mask=jnp.ones((cohort.size,), jnp.float32),
            records=records,
            step=step, evaluate=evaluate,
            # targets are constant per cohort: pad + upload once, slice
            # per minibatch on device
            t_train_pad=jnp.asarray(_pad_targets(t_train, spec0.layers[-1])),
            t_eval_pad=jnp.asarray(_pad_targets(t_eval, spec0.layers[-1]))))
    ledger.meta["programs_reused"] = reused
    return ledger, states, jnp.asarray(x_train), jnp.asarray(x_eval)


def run_sweep(specs: Sequence[pop.CandidateSpec], x_train, t_train,
              x_eval, t_eval, cfg: SweepConfig, *,
              tag: str = "",
              recorder: "obs.Recorder | None" = None) -> SweepResult:
    """Train all candidates population-parallel and successively halve.

    x_* [N, n_in] float, t_* [N, n_classes] one-hot (padded per cohort to
    its output width).  Returns the lineage ledger (winner marked) and
    the final cohort states.

    ``recorder`` (obs.Recorder) gets one ``obs.SweepRound`` event per
    scheduler decision — rank (once per round, the scored table in
    ``detail``), prune and quarantine (one per affected member, its
    cohort/slot attached), winner — so a sweep's ledger and its
    telemetry share one timeline.  All values are host floats the
    scheduler already fetched for ranking.

    ``ledger.meta`` counts the call's traces of the cohorts' step and
    eval (``step_traces``, ``eval_traces``: one per cohort of distinct
    shapes on a cold call, 0 on a warm one) and the cohorts whose step
    and eval came from the program cache (``programs_reused``, also
    counted as ``sweep.programs_reused`` on the recorder)."""
    specs = list(specs)
    traces0 = dict(_TRACES.counts)
    with obs.span("sweep.setup", recorder):
        ledger, states, x_train_d, x_eval_d = _setup(
            specs, x_train, t_train, x_eval, t_eval, cfg, tag)
    if recorder is not None:
        recorder.count("sweep.programs_reused",
                       ledger.meta["programs_reused"])

    n_train = x_train_d.shape[0]
    global_step = 0
    n_live = len(specs)
    for rnd in range(cfg.rounds):
        # -- train: steps_per_round E-batched steps per cohort, shared data
        for _ in range(cfg.steps_per_round):
            bi = jnp.asarray(_batch_indices(
                n_train, min(cfg.batch_size, n_train), global_step))
            xb = jnp.take(x_train_d, bi, axis=0)
            # a cohort's first step of the call: on a cold call it also
            # traces, lowers and fetches the step
            step_span = "sweep.first_step" if global_step == 0 else \
                "sweep.step"
            for st in states:
                if not any(r.pruned_at is None for r in st.records):
                    continue        # whole cohort pruned: steps are no-ops
                with obs.span(step_span, recorder):
                    if st.is_adam:
                        # stamp the per-step bias-correction time into
                        # every row: all live members step in lockstep,
                        # and on a quarantined (zeroed) row t is harmless
                        # — lr = 0 and the masked gradients are exact
                        # zeros, so the kernels still write w' = w,
                        # m' = v' = 0
                        from repro.kernels import block_sparse_matmul as bsm
                        st.hyp = st.hyp.at[:, bsm.COL_T].set(
                            jnp.float32(global_step + 1))
                    out = st.step(
                        st.params, st.mom, st.hyp, st.mask, xb,
                        jnp.take(st.t_train_pad, bi, axis=0))
                    with obs.span("sweep.fetch", recorder):
                        if cfg.quarantine:
                            st.params, st.mom, losses, health = out
                            health = np.asarray(health)
                        else:
                            st.params, st.mom, losses = out
                            health = None
                        losses = np.asarray(losses)
                    for rec, loss in zip(st.records, losses):
                        if rec.pruned_at is None:
                            rec.loss_curve.append(float(loss))
                            if cfg.quarantine and (
                                    not math.isfinite(float(loss))
                                    or health[rec.slot] > 0):
                                _quarantine(st, rec, rnd, global_step,
                                            recorder=recorder)
            global_step += 1

        # -- eval: vectorized per-member loss, live members only ranked
        scored = []      # (width-normalized score, cohort_idx, slot)
        for ci, st in enumerate(states):
            if not any(r.pruned_at is None for r in st.records):
                continue
            with obs.span("sweep.eval", recorder):
                ev = np.asarray(st.evaluate(st.params, x_eval_d,
                                            st.t_eval_pad))
                for rec, loss in zip(st.records, ev):
                    if rec.pruned_at is None:
                        rec.eval_losses.append(float(loss))
                        rec.rounds_survived = rnd + 1
                        scored.append((_score(loss, st.out_width), ci,
                                       rec.slot))
        with obs.span("sweep.prune", recorder):
            n_live = _rank_and_halve(states, scored, rnd, cfg, n_live,
                                     recorder)

    # -- winner: best width-normalized final eval score among survivors
    best = min(((_score(m.eval_losses[-1], st.out_width), m.member)
                for st in states for m in st.records
                if m.pruned_at is None and m.eval_losses), default=None)
    if best is not None and math.isfinite(best[0]):
        for m in ledger.members:
            m.winner = m.member == best[1]
        if recorder is not None:
            w = next(m for m in ledger.members if m.winner)
            recorder.emit(obs.SweepRound(
                action="winner", round=cfg.rounds - 1, member=w.member,
                cohort=w.cohort, slot=w.slot,
                detail={"score": best[0]}))
    ledger.meta["live_at_end"] = n_live
    ledger.meta["quarantined"] = sum(
        1 for m in ledger.members if m.quarantined_at is not None)
    ledger.meta["step_traces"] = _TRACES.counts["step"] - traces0["step"]
    ledger.meta["eval_traces"] = _TRACES.counts["eval"] - traces0["eval"]
    return SweepResult(ledger=ledger, states=states)


def _rank_and_halve(states, scored, rnd, cfg, n_live, recorder):
    """Emit the round's rank event and, before the last round, keep the
    globally best keep_fraction of live members and zero the rest.
    Returns the live count."""
    if recorder is not None and scored:
        recorder.emit(obs.SweepRound(
            action="rank", round=rnd,
            detail={"live": len(scored), "scores": [
                {"member": states[ci].records[slot].member,
                 "cohort": ci, "slot": slot,
                 "score": s if math.isfinite(s) else None}
                for s, ci, slot in sorted(scored)]}))
    if rnd >= cfg.rounds - 1 or len(scored) <= 1:
        return n_live
    scored.sort()
    n_keep = max(1, int(math.ceil(len(scored) * cfg.keep_fraction)))
    for sc, ci, slot in scored[n_keep:]:
        st = states[ci]
        st.mask = st.mask.at[slot].set(0.0)
        st.hyp = st.hyp.at[slot].set(0.0)
        st.records[slot].pruned_at = rnd
        if recorder is not None:
            recorder.count("sweep.pruned")
            recorder.emit(obs.SweepRound(
                action="prune", round=rnd,
                member=st.records[slot].member, cohort=ci, slot=slot,
                detail={"score": sc if math.isfinite(sc) else None}))
    return n_keep
