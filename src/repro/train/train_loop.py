"""Production train loop: checkpoint/restart, divergence guardian,
straggler watch, metrics.

Fault-tolerance contract:
  * auto-resume from the newest VERIFIABLE checkpoint (params, optimizer,
    data-iterator state, step — bitwise identical continuation; a
    corrupted latest checkpoint falls back to the next-newest),
  * async checkpoint every ``ckpt_every`` steps + always on exit, with
    optional ``keep_last_k`` retention GC,
  * crash injection hook for tests (``fail_at_step``),
  * straggler mitigation: per-step wall-times tracked in a rolling window;
    steps slower than ``straggler_factor`` x median raise an alarm through
    ``on_straggler`` (at fleet scale this triggers hot-spare swap; here it
    is logged and counted — the decision logic is what we can test without
    hardware).

Divergence guardian (``GuardianConfig``): the fused BP+UP path updates
weights in-place inside the kernels — one non-finite dw destroys the
parameter state with no HBM gradient left to inspect.  The guardian
closes the loop around the in-kernel detector (metrics["nonfinite"],
kernels/block_sparse_matmul.py health flags) plus loss sentinels:

  * **sentinels** — trip on a non-finite loss, on nonfinite > 0 (the
    update just wrote non-finite parameters), or on a loss spike beyond
    ``spike_factor`` x the rolling-window median;
  * **healthy promotion** — a checkpoint becomes a rollback target only
    after SURVIVING ``health_window`` further steps without a trip
    (a checkpoint written next to silent corruption must never be
    restored into);
  * **rollback + backoff** — on trip: restore the latest healthy-marked
    checkpoint, shrink the effective lr by ``lr_backoff`` (threaded
    through the train step's ``lr_scale`` operand — the fused path folds
    it into the existing hyp table, no retrace), skip the offending
    batch on replay, and retry;
  * **bounded retries** — after ``max_retries`` trips the loop raises
    ``GuardianTripped`` with the full trip history instead of looping
    forever on an unrecoverable run.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from pathlib import Path
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.models import moe as moe_mod
from repro.obs import telemetry as obs
from repro.train import checkpoint as ckpt_mod


@dataclasses.dataclass
class GuardianConfig:
    window: int = 32            # rolling loss window for the spike sentinel
    spike_factor: float = 10.0  # trip when loss > factor * window median
    min_history: int = 8        # spike sentinel armed after this many losses
    health_window: int = 10     # steps a checkpoint must survive → healthy
    lr_backoff: float = 0.5     # lr_scale multiplier per trip
    max_retries: int = 3        # trips before giving up
    skip_offending_batch: bool = True


class GuardianTripped(RuntimeError):
    """Raised when the guardian exhausts ``max_retries`` — the run is not
    recoverable by rollback + backoff alone."""

    def __init__(self, msg: str, trips: list[dict]):
        super().__init__(msg)
        self.trips = trips


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int
    ckpt_dir: str
    ckpt_every: int = 100
    log_every: int = 10
    straggler_window: int = 50
    straggler_factor: float = 3.0
    fail_at_step: Optional[int] = None      # test hook: simulated crash
    guardian: Optional[GuardianConfig] = None
    keep_last_k: Optional[int] = None       # retention GC (None = keep all)
    full_checksum: bool = False             # digest every byte at save time


class StragglerMonitor:
    def __init__(self, window: int, factor: float,
                 on_straggler: Callable[[int, float, float], None] | None = None):
        self.times = deque(maxlen=window)
        self.factor = factor
        self.count = 0
        self.on_straggler = on_straggler or (lambda *a: None)

    def observe(self, step: int, dt: float):
        if len(self.times) >= 8:
            med = float(np.median(self.times))
            if dt > self.factor * med:
                self.count += 1
                self.on_straggler(step, dt, med)
        self.times.append(dt)


def _batch_tokens(batch) -> int:
    """Token count of a host-side batch for tokens/s: the ``tokens``
    field's element count when present (LM pipelines), else the leading
    dim of the first array leaf (generic supervised batches)."""
    if isinstance(batch, dict) and "tokens" in batch:
        return int(np.asarray(batch["tokens"]).size)
    leaves = jax.tree.leaves(batch)
    return int(np.asarray(leaves[0]).shape[0]) if leaves else 0


def _moe_counters(metrics) -> dict:
    """An MoE step's expert-layer counters (``models/moe.STATS``: rows
    routed to held experts, rows the computed tiles cover, the largest
    expert's rows, slots dropped) as host ints, fetched after the loss
    has already synced the step."""
    names = [k for k in moe_mod.STATS if k in metrics]
    if not names:
        return {}
    vals = jax.device_get([metrics[k] for k in names])
    return {k: int(v) for k, v in zip(names, vals)}


def _restore_into(cfg, step, state_like, pipeline):
    tree, extra = ckpt_mod.restore(cfg.ckpt_dir, step, state_like)
    pipeline.step = extra["data_state"]["step"]
    pipeline.seed = extra["data_state"]["seed"]
    return tree["params"], tree["opt"], extra["step"]


def run(cfg: TrainLoopConfig, train_step, params, opt_state, pipeline,
        log: Callable[[str], None] = print,
        recorder: "obs.Recorder | None" = None) -> dict:
    """Returns {params, opt_state, step, history, straggler_count, guardian,
    moe}.

    ``train_step(params, opt_state, batch, step[, lr_scale]) ->
    (params, opt_state, metrics)`` must be jit-compiled by the caller
    (with shardings attached for multi-device runs); the 5-arg form
    (train/steps.make_train_step provides it) is required only when a
    ``GuardianConfig`` is set.  ``pipeline`` is a restartable iterator
    with ``state()`` / seed+step attributes (data/pipeline.py).

    ``recorder`` (obs.Recorder) gets one ``TrainStep`` event per ADOPTED
    step plus ``Guardian`` (trip/rollback/backoff/recovery) and
    ``Checkpoint`` (save/promote/gc) lifecycle events.  No-extra-device-
    sync: every recorded value is one the loop already fetched for its
    own logic — ``loss`` is synced for honest step timing regardless,
    ``nonfinite`` only on the guardian path (``obs.NOT_SAMPLED`` when
    the guardian is off rather than forcing a transfer).  An MoE step's
    counters (``moe_*``) are fetched after the loss's sync and go to the
    recorder as counters (the largest expert's rows as a gauge); the
    returned ``moe`` sums them over the adopted steps (that one: the max).
    """
    g = cfg.guardian
    saver = ckpt_mod.AsyncSaver()
    state_like = {"params": params, "opt": opt_state}

    def _save_extra():
        return {"step": step, "data_state": pipeline.state()}

    start_step = 0
    found, tree, extra = ckpt_mod.restore_latest(cfg.ckpt_dir, state_like,
                                                 log=log)
    if found is not None:
        params, opt_state = tree["params"], tree["opt"]
        start_step = extra["step"]
        pipeline.step = extra["data_state"]["step"]
        pipeline.seed = extra["data_state"]["seed"]
        log(f"[train] resumed from step {start_step}")

    step = start_step
    # guardian state
    lr_scale = 1.0
    trips: list[dict] = []
    bad_data_steps: set[int] = set()
    loss_win: deque = deque(maxlen=g.window) if g else deque()
    pending_healthy: list[int] = []
    if g is not None and ckpt_mod.latest_healthy_step(cfg.ckpt_dir) is None:
        # anchor: the pre-training (or just-resumed) state is the rollback
        # floor until a later checkpoint survives the health window
        if found is None:
            ckpt_mod.save(cfg.ckpt_dir, step,
                          {"params": params, "opt": opt_state},
                          extra=_save_extra(),
                          full_checksum=cfg.full_checksum)
        ckpt_mod.mark_healthy(cfg.ckpt_dir, step)

    mon = StragglerMonitor(cfg.straggler_window, cfg.straggler_factor,
                           on_straggler=lambda s, dt, med: log(
                               f"[straggler] step {s}: {dt*1e3:.1f}ms vs median {med*1e3:.1f}ms"))
    history = []
    moe_totals: dict = {}
    rec = recorder
    dt_ema: float | None = None
    awaiting_recovery = False
    try:
        while step < cfg.total_steps:
            if cfg.fail_at_step is not None and step == cfg.fail_at_step:
                raise RuntimeError(f"injected failure at step {step}")
            data_step = pipeline.state()["step"] if g is not None else None
            batch = next(pipeline)
            if g is not None and data_step in bad_data_steps:
                log(f"[guardian] skipping poisoned batch "
                    f"(data step {data_step})")
                continue
            t0 = time.perf_counter()
            args = (params, opt_state,
                    jax.tree.map(jax.numpy.asarray, batch),
                    jax.numpy.asarray(step))
            if g is not None:
                new_params, new_opt, metrics = train_step(
                    *args, jax.numpy.float32(lr_scale))
            else:
                new_params, new_opt, metrics = train_step(*args)
            loss = float(metrics["loss"])   # blocks: honest step timing
            dt = time.perf_counter() - t0

            if g is not None:
                nonfinite = float(metrics.get("nonfinite", 0.0))
                why = None
                if not np.isfinite(loss):
                    why = f"non-finite loss {loss}"
                elif nonfinite > 0:
                    why = (f"{int(nonfinite)} non-finite update "
                           "leaves/tiles (in-kernel health flags)")
                elif len(loss_win) >= g.min_history:
                    med = float(np.median(loss_win))
                    if loss > g.spike_factor * max(med, 1e-12):
                        why = (f"loss spike {loss:.4g} > "
                               f"{g.spike_factor}x median {med:.4g}")
                if why is not None:
                    # the offending update is DISCARDED (new_params never
                    # adopted); roll back to the last healthy checkpoint
                    trips.append({"step": step, "data_step": data_step,
                                  "reason": why, "lr_scale": lr_scale})
                    if rec is not None:
                        rec.count("train.guardian.trips")
                        rec.emit(obs.Guardian(
                            action="trip", step=step,
                            detail={"reason": why, "data_step": data_step,
                                    "lr_scale": lr_scale}))
                    if g.skip_offending_batch:
                        bad_data_steps.add(data_step)
                    if len(trips) > g.max_retries:
                        raise GuardianTripped(
                            f"guardian exhausted {g.max_retries} retries; "
                            f"last trip at step {step}: {why} "
                            f"(trip history: {trips})", trips)
                    saver.wait()
                    h = ckpt_mod.latest_healthy_step(cfg.ckpt_dir)
                    if h is None:
                        raise GuardianTripped(
                            f"guardian tripped at step {step} ({why}) with "
                            "no healthy checkpoint to roll back to", trips)
                    tripped_at = step
                    params, opt_state, step = _restore_into(
                        cfg, h, state_like, pipeline)
                    lr_scale *= g.lr_backoff
                    loss_win.clear()
                    pending_healthy.clear()
                    if rec is not None:
                        rec.emit(obs.Guardian(
                            action="rollback", step=step,
                            detail={"from_step": tripped_at}))
                        rec.emit(obs.Guardian(
                            action="backoff", step=step,
                            detail={"lr_scale": lr_scale}))
                        rec.gauge("train.lr_scale", lr_scale)
                    awaiting_recovery = True
                    log(f"[guardian] TRIP: {why} — rolled back to healthy "
                        f"step {step}, lr_scale -> {lr_scale:.4g}, retry "
                        f"{len(trips)}/{g.max_retries}")
                    continue
                loss_win.append(loss)

            params, opt_state = new_params, new_opt
            mon.observe(step, dt)
            for name, n in _moe_counters(metrics).items():
                big = name == "moe_max_expert_rows"
                moe_totals[name] = (max(moe_totals.get(name, 0), n) if big
                                    else moe_totals.get(name, 0) + n)
                if rec is not None:
                    if big:
                        rec.gauge(f"train.{name}", n)
                    else:
                        rec.count(f"train.{name}", n)
            if rec is not None:
                if awaiting_recovery:
                    # first step adopted after a rollback: the run is live
                    # again at the reduced lr
                    rec.emit(obs.Guardian(
                        action="recovery", step=step,
                        detail={"trips": len(trips),
                                "lr_scale": lr_scale}))
                    awaiting_recovery = False
                dt_ema = dt if dt_ema is None else 0.9 * dt_ema + 0.1 * dt
                n_tok = _batch_tokens(batch)
                rec.count("train.steps")
                rec.observe("train.dt_s", dt)
                rec.emit(obs.TrainStep(
                    step=step, loss=loss,
                    nonfinite=(nonfinite if g is not None
                               else obs.NOT_SAMPLED),
                    lr_scale=lr_scale, dt_s=dt, dt_ema_s=dt_ema,
                    tokens_per_s=(n_tok / dt if dt > 0 else 0.0)))
            step += 1
            if step % cfg.log_every == 0 or step == cfg.total_steps:
                history.append({"step": step, "loss": loss, "dt_s": dt})
                log(f"[train] step {step} loss {loss:.4f} ({dt*1e3:.0f} ms)")
            if step % cfg.ckpt_every == 0:
                saver.save(cfg.ckpt_dir, step,
                           {"params": params, "opt": opt_state},
                           extra=_save_extra(),
                           full_checksum=cfg.full_checksum)
                if rec is not None:
                    rec.count("train.ckpt.saves")
                    rec.emit(obs.Checkpoint(action="save", step=step,
                                            detail={"async": True}))
                if g is not None:
                    pending_healthy.append(step)
                if cfg.keep_last_k is not None:
                    removed = ckpt_mod.gc_checkpoints(
                        cfg.ckpt_dir, cfg.keep_last_k, log=log)
                    if rec is not None and removed:
                        rec.emit(obs.Checkpoint(
                            action="gc", step=step,
                            detail={"removed": list(removed)}))
            if g is not None:
                # promote checkpoints that survived the health window
                while pending_healthy and (
                        pending_healthy[0] + g.health_window <= step):
                    s = pending_healthy[0]
                    comp = ckpt_mod.complete_steps(cfg.ckpt_dir)
                    if s in comp:
                        ckpt_mod.mark_healthy(cfg.ckpt_dir, s)
                        pending_healthy.pop(0)
                        if rec is not None:
                            rec.emit(obs.Checkpoint(
                                action="promote", step=s,
                                detail={"survived": g.health_window}))
                    elif comp and s < comp[-1]:
                        pending_healthy.pop(0)   # overwritten or GC'd
                    else:
                        break                    # async write still in flight
    finally:
        saver.wait()
        ckpt_mod.save(cfg.ckpt_dir, step,
                      {"params": params, "opt": opt_state},
                      extra=_save_extra(), full_checksum=cfg.full_checksum)
        if rec is not None:
            rec.emit(obs.Checkpoint(action="save", step=step,
                                    detail={"final": True}))
        if cfg.keep_last_k is not None:
            removed = ckpt_mod.gc_checkpoints(cfg.ckpt_dir, cfg.keep_last_k,
                                              log=log)
            if rec is not None and removed:
                rec.emit(obs.Checkpoint(action="gc", step=step,
                                        detail={"removed": list(removed)}))
    guardian_info = {"trips": trips, "lr_scale": lr_scale,
                     "skipped_data_steps": sorted(bad_data_steps)}
    return {"params": params, "opt_state": opt_state, "step": step,
            "history": history, "straggler_count": mon.count,
            "guardian": guardian_info if g is not None else None,
            "moe": moe_totals}
