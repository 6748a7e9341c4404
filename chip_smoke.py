#!/usr/bin/env python3
"""Chip smoke: the train, serve and sweep paths end to end on a TPU.

    python chip_smoke.py             # the four phases below, on one chip
    python chip_smoke.py --chips 4   # data-parallel training: 4 chips vs 1

The model is stablelm-3b at its published widths (d_model 2560, 32 heads
x head_dim 80, d_ff 6912, vocab 50304) with the paper's pre-defined sparse
FFN junctions (density 0.25, block 128, as ``launch/train.py --sparse``
sets them).  The only cut is depth (``LAYERS``).  Weights are random from
a fixed seed, data comes from the seeded pipelines, and checkpoints go to
a temporary directory.

Phases, all in this one process on one chip:

1. train, two-pass: the launcher's default step (fp32 params, fused update
   off) built by ``train/steps.make_train_step`` and run through
   ``train/train_loop.run``; its first loss is checked against the jnp
   engine's loss on the same params and batch.
2. train, fused BP+UP: bf16 params, the optimizer update inside the dw
   kernels; its losses and its first update must match phase 1's.
3. serve: ``ContinuousEngine`` on the pallas engine, plain and int8; every
   request completes, each jitted step traces once, and the compiled
   decode tick holds ``flash_decode`` and the junction kernel.
   ``flash_decode`` is checked against ``paged_decode_ref`` at the served
   pool shape.
4. sweep: the population scheduler through ``launch/sweep.py`` at its
   default widths (E=3 cohorts, fused pallas path); it names a winner.

Training feeds ``LMTokenPipeline``'s first batch at every step, at a
constant lr, so each loss after the first shows what the previous update
did.  Each run takes one step, then resumes from the checkpoint the loop
wrote for the rest; the update of that first step is compared leaf by
leaf between the two paths that are checked against each other.

With ``--chips 4`` only the data-parallel form of phase 1's step runs
(``launch/train.shard_train_step`` at data=4, as ``launch/train.py --data
4`` builds it), then the same step on one chip of the same process, on the
same params and batch, both through ``train_loop.run``.

Each phase prints its compile seconds, results, device memory and step
wall times.  The times are smoke timings, not benchmark numbers.  The
last line is the JSON object ``{"ok": true, "device": {...}}``.  When JAX
finds no TPU, or any check fails, the script exits non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "stablelm-3b"
LAYERS = 8              # the depth cut, 32 -> 8; every width is published
BATCH, SEQ = 8, 1024
TRAIN_STEPS = 4
# constant: a warmup would make the first update, the one compared between
# paths, zero.  One Adam step moves a weight by about LR, several bf16 ulps
# of an init-scale weight (std 0.02-0.04).
LR = 1e-3
SEED = 0
# Limits, from readings on TPU v5 lite chips at these shapes.  Relative
# loss gap between two datapaths on the same batch and weights: sound
# paths read 0 to 1.5e-5 (kernels vs the jnp engine), a model missing one
# FFN's output reads 1.5e-4 (checked on every run below).
LOSS_RTOL = 5e-5
# ... once the weights have taken updates that round differently: fused
# (bf16) vs two-pass (fp32) reads 2.3e-4 to 4.3e-4 over three updates,
# data=4 vs one chip 5.9e-5 after one.  Dropping one FFN moves the loss
# less than that drift; the first-update check below is what sees the
# update kernels.
DRIFT_RTOL = 2e-3
# Per-leaf gap between two paths' first updates, relative to the update's
# norm: Adam's first step is about lr * sign(g), so gradients within bf16
# rounding of zero flip between paths.  Fused vs two-pass reads 2.4e-2 to
# 4.7e-2, data=4 vs one chip 3.6e-2 to 0.14; an update written as zero or
# doubled reads 1, a negated one 2.
UPDATE_RTOL = 0.3
TICK_REPS = 20
SERVE_REQUESTS, PROMPT_LEN, MAX_NEW = 12, 512, 64
SERVE_SLOTS, PAGE_SIZE, PREFILL_CHUNK = 8, 16, 128


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


class CompileMeter:
    """Seconds of XLA backend compilation (or of fetching the executable
    from the persistent cache) since ``reset``, from jax.monitoring.
    Tracing is left out: a nested jitted function's trace overlaps its
    caller's, so summing trace events would count it twice."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def reset(self):
        self.secs, self.compiles, self.cache_hits = 0.0, 0, 0

    def _duration(self, event, duration, **_):
        if event == self.BACKEND:
            self.secs += duration
            self.compiles += 1

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"

    def report(self, phase: str):
        log(f"{phase}: backend compile {self.secs:.1f} s ({self.compiles} "
            f"programs, {self.cache_hits} persistent-cache hits)")


def memory(phase: str, devices) -> list[int]:
    """Print and return each device's peak_bytes_in_use (process-wide)."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
        log(f"{phase}: device {d.id} bytes_in_use "
            f"{int(st.get('bytes_in_use', 0)) / 2**30:.2f} GiB, "
            f"peak_bytes_in_use {peaks[-1] / 2**30:.2f} GiB")
    return peaks


def compiled_text(compiled, phase: str, kernels=()) -> None:
    """The phase's program runs Pallas kernels compiled for the chip."""
    txt = compiled.as_text()
    check("tpu_custom_call" in txt, f"{phase}: no tpu_custom_call")
    for k in kernels:
        check(k in txt, f"{phase}: kernel {k} not in the compiled step")
    ma = compiled.memory_analysis()
    log(f"{phase}: compiled memory: arguments "
        f"{ma.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
        f"{ma.temp_size_in_bytes / 2**30:.2f} GiB, aliased "
        f"{ma.alias_size_in_bytes / 2**30:.2f} GiB")


def model_config(**kw):
    from repro.configs import registry
    from repro.core.sparsity import SparsityConfig
    cfg = registry.get(ARCH).with_sparsity(
        SparsityConfig(density=0.25, block=128, where="ffn"))
    return dataclasses.replace(cfg, n_layers=LAYERS, engine="pallas", **kw)


def train_optimizer():
    """The launcher's default optimizer (``launch/train.py --optim adam``)
    at a constant lr."""
    from repro.optim import constant_schedule, fused_adam
    return fused_adam(constant_schedule(LR), grad_clip=1.0)


def init_params(cfg, dtype=None):
    """Seeded params; ``dtype`` casts the fp32 init so two phases start
    from the same weights."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    params = M.init(dataclasses.replace(cfg, param_dtype="float32"),
                    jax.random.PRNGKey(SEED))
    if dtype is None:
        return params
    return jax.tree.map(lambda p: p.astype(dtype)
                        if jnp.issubdtype(p.dtype, jnp.floating) else p,
                        params)


# ------------------------------------------------------------------ train
def repeated_batches(cfg):
    """``LMTokenPipeline`` that yields its first batch at every step."""
    from repro.data.pipeline import LMTokenPipeline

    class Repeated(LMTokenPipeline):
        def _make(self, step):
            return super()._make(0)

    return Repeated(cfg, BATCH, SEQ, seed=SEED)


def train_run(name, cfg, step, params, opt_state, meter, kernels, steps):
    """Compile ``step`` ahead (checking its kernels), then run it through
    ``train_loop.run``: one step, then up to ``steps``, resuming from the
    checkpoint the first run wrote.  Returns the per-step losses and the
    params before and after the first update, on the host."""
    import jax
    import jax.numpy as jnp
    from repro.train.train_loop import TrainLoopConfig, run

    meter.reset()
    batch = jax.tree.map(jnp.asarray, next(repeated_batches(cfg)))
    compiled = step.lower(params, opt_state, batch, jnp.asarray(0)).compile()
    meter.report(name)
    compiled_text(compiled, name, kernels)
    p0, history = jax.device_get(params), []
    with tempfile.TemporaryDirectory() as ckpt:
        for total in (1, steps):
            res = run(TrainLoopConfig(total_steps=total, ckpt_dir=ckpt,
                                      ckpt_every=10**9, log_every=1,
                                      keep_last_k=1),
                      compiled, params, opt_state, repeated_batches(cfg),
                      log=lambda m: log(f"{name}: {m}"))
            history += res["history"]
            if total == 1:
                p1 = jax.device_get(res["params"])
            # the next run restores its state; it reads only the structure
            params, opt_state = (
                jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                             t) for t in (res["params"], res["opt_state"]))
            del res
            gc.collect()
    losses = [h["loss"] for h in history]
    check(len(losses) == steps, f"{name}: {len(losses)} steps ran")
    check(all(map(math.isfinite, losses)), f"{name}: non-finite loss {losses}")
    check(losses[1] < losses[0],
          f"{name}: the first update did not lower the loss {losses}")
    log(f"{name}: losses {losses}")
    log(f"{name}: smoke timing, not a benchmark: step wall s "
        f"{[h['dt_s'] for h in history]}")
    return losses, p0, p1


def losses_agree(name, got, want, rtol=LOSS_RTOL):
    gaps = [abs(a - b) / abs(b) for a, b in zip(got, want)]
    log(f"{name}: losses {got} vs {want}: relative gaps {gaps}")
    check(max(gaps) <= rtol, f"{name}: loss gap {max(gaps)} > {rtol}")


def update_gap(name, got0, got1, want0, want1):
    """Compare path A's first update (got1 - got0) with path B's (want1 -
    want0) applied to A's weights in A's dtype, per float leaf, relative
    to the latter's norm.  A leaf that moved in neither (a bf16 weight
    whose update is under half an ulp) is left out; every FFN junction
    weight must have moved."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    gaps = {}
    for (path, a0), a1, b0, b1 in zip(
            jax.tree_util.tree_leaves_with_path(got0), jax.tree.leaves(got1),
            jax.tree.leaves(want0), jax.tree.leaves(want1)):
        if not jnp.issubdtype(a0.dtype, jnp.floating):
            continue
        x0 = np.asarray(a0, np.float32)
        got = np.asarray(a1, np.float32) - x0
        want = (x0 + (np.asarray(b1, np.float32) - np.asarray(b0, np.float32))
                ).astype(a1.dtype).astype(np.float32) - x0
        nw = float(np.linalg.norm(want))
        nd = float(np.linalg.norm(got - want))
        if nw or nd:
            gaps[jax.tree_util.keystr(path)] = nd / nw if nw else math.inf
    log(f"{name}: first-update gap per leaf {gaps}")
    moved = [k for k in gaps if "['mlp']" in k]
    check(len(moved) == 3, f"{name}: FFN junction weights that moved: {moved}")
    worst = max(gaps, key=gaps.get)
    check(gaps[worst] <= UPDATE_RTOL,
          f"{name}: first update of {worst} off by {gaps[worst]}")


def without_ffn(params, layer):
    """The params with one layer's FFN output junction zeroed."""
    mlp = params["layers"]["mlp"]
    wo = {**mlp["wo"], "w": mlp["wo"]["w"].at[layer].set(0)}
    return {**params, "layers": {**params["layers"],
                                 "mlp": {**mlp, "wo": wo}}}


def phase_train(meter, dev):
    import jax
    import jax.numpy as jnp
    from repro.train.steps import (fused_update_eligible, make_eval_step,
                                   make_train_step)

    cfg = model_config()
    opt = train_optimizer()
    params = init_params(cfg)
    batch0 = jax.tree.map(jnp.asarray, next(repeated_batches(cfg)))
    # the jnp engine (gather + einsum, no kernels) on the same batch, and
    # on a model missing one FFN: the loss tolerance must catch the latter
    meter.reset()
    ev = jax.jit(make_eval_step(dataclasses.replace(cfg, engine="jnp")))
    ref = float(ev(params, batch0)["loss"])
    meter.report("reference (jnp engine)")
    fault = float(ev(without_ffn(params, LAYERS // 2), batch0)["loss"])
    fault_gap = abs(fault - ref) / abs(ref)
    log(f"reference (jnp engine): loss {ref}; without layer {LAYERS // 2}'s "
        f"FFN output {fault} (relative gap {fault_gap})")
    check(fault_gap > LOSS_RTOL, f"LOSS_RTOL {LOSS_RTOL} passes a model "
          f"missing one FFN (gap {fault_gap})")

    name = "phase 1 train two-pass"
    ok, why = fused_update_eligible(cfg, opt)
    log(f"{name}: update path two-pass ({why})")
    check(not ok, f"{name}: fused update eligible")
    two, p0, p1 = train_run(name, cfg, make_train_step(cfg, opt), params,
                            opt.init(params), meter,
                            ("junction_fwd", "junction_dw", "junction_dx"),
                            TRAIN_STEPS)
    losses_agree(f"{name} first loss vs jnp engine", two[:1], [ref])
    memory(name, [dev])
    del params
    gc.collect()

    name = "phase 2 train fused"
    cfg2 = model_config(fused_update=True, dtype="bfloat16",
                        param_dtype="bfloat16")
    ok, why = fused_update_eligible(cfg2, opt)
    log(f"{name}: update path fused BP+UP ({why})")
    check((ok, why) == (True, "fused"), f"{name}: eligibility {(ok, why)}")
    params = init_params(cfg2, jnp.bfloat16)
    fused, q0, q1 = train_run(name, cfg2, make_train_step(cfg2, opt), params,
                              opt.init(params), meter,
                              ("junction_update_dw",), TRAIN_STEPS)
    losses_agree(f"{name} vs two-pass, first loss", fused[:1], two[:1])
    losses_agree(f"{name} vs two-pass, after updates", fused[1:], two[1:],
                 DRIFT_RTOL)
    update_gap(f"{name} vs two-pass", q0, q1, p0, p1)
    memory(name, [dev])
    gc.collect()


# ------------------------------------------------------------------ serve
def decode_kernel_check(cfg):
    """flash_decode vs its jnp reference at the served pool shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import flash_attention as fa
    maxp = -(-(PROMPT_LEN + MAX_NEW) // PAGE_SIZE)
    hkv, rep, hd = cfg.kv_heads, cfg.n_heads // cfg.kv_heads, cfg.head_dim
    P = SERVE_SLOTS * maxp + 1
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(ks[0], (SERVE_SLOTS, hkv, rep, hd), jnp.bfloat16)
    kp, vp = (jax.random.normal(k, (P, PAGE_SIZE, hkv * hd), jnp.bfloat16)
              for k in ks[1:])
    pt = jnp.asarray(1 + np.arange(SERVE_SLOTS * maxp, dtype=np.int32)
                     .reshape(SERVE_SLOTS, maxp))
    lens = jnp.asarray(np.linspace(0, maxp * PAGE_SIZE, SERVE_SLOTS)
                       .astype(np.int32))
    kern = jax.jit(fa.flash_decode).lower(q, kp, vp, pt, lens).compile()
    compiled_text(kern, "phase 3 flash_decode")
    got = np.asarray(kern(q, kp, vp, pt, lens), np.float32)
    ms = wall_ms(lambda: kern(q, kp, vp, pt, lens))
    log(f"phase 3 flash_decode: smoke timing, not a benchmark: "
        f"{ms} (lengths 0..{maxp * PAGE_SIZE})")
    want = np.asarray(jax.jit(fa.paged_decode_ref)(q, kp, vp, pt, lens),
                      np.float32)
    err = float(np.max(np.abs(got - want)))
    # bf16 output rounding on values of order 1
    check(err <= 2e-2, f"flash_decode vs reference max abs err {err}")
    log(f"phase 3 serve: flash_decode vs paged_decode_ref at "
        f"{SERVE_SLOTS}x{maxp} pages of {PAGE_SIZE}x{hkv}x{hd}: "
        f"max abs err {err}")


def wall_ms(fn) -> str:
    """Median, min and max wall ms of TICK_REPS calls after two warm-up
    calls, each waited for as a caller that reads the result would."""
    import jax
    import numpy as np
    dts = []
    for _ in range(TICK_REPS + 2):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        dts.append(1e3 * (time.perf_counter() - t0))
    dts = dts[2:]
    return (f"median {float(np.median(dts))} ms, min {min(dts)}, "
            f"max {max(dts)} over {len(dts)} calls")


def decode_tick_check(eng, name, kernels):
    """The engine's jitted decode tick, at the shapes serve() ran it,
    compiles to the named kernels; its wall time with every slot at the
    pool's full length."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import model as M
    maxp, num_pages = eng.pages_per_slot, eng.stats["num_pages"]
    pool = M.make_paged_cache(eng.cfg, num_pages, PAGE_SIZE)
    tok = jnp.zeros((SERVE_SLOTS, 1), jnp.int32)
    pos = jnp.full((SERVE_SLOTS,), maxp * PAGE_SIZE - 1, jnp.int32)
    pt = jnp.asarray((1 + np.arange(SERVE_SLOTS * maxp) % (num_pages - 1))
                     .reshape(SERVE_SLOTS, maxp).astype(np.int32))
    key = jax.random.PRNGKey(SEED)
    compiled = eng._tick.lower(eng.params, pool, tok, pos, pt, key).compile()
    compiled_text(compiled, f"{name} decode tick", kernels)

    def tick():
        nonlocal pool
        out, _, pool = compiled(eng.params, pool, tok, pos, pt, key)
        return out
    log(f"{name} decode tick: smoke timing, not a benchmark: "
        f"{SERVE_SLOTS} slots at {maxp * PAGE_SIZE} tokens: {wall_ms(tick)}")


def phase_serve(meter, dev):
    import numpy as np
    from repro.serve.engine import ContinuousEngine, Request, ServeConfig

    cfg = model_config()
    decode_kernel_check(cfg)
    params = init_params(cfg)
    rng = np.random.default_rng(SEED)
    prompts = rng.integers(0, cfg.vocab, (SERVE_REQUESTS, PROMPT_LEN),
                           dtype=np.int32)
    for quant in (None, "int8"):
        name = f"phase 3 serve quantize={quant or 'off'}"
        scfg = ServeConfig(engine="pallas", quantize=quant,
                           max_new_tokens=MAX_NEW, slots=SERVE_SLOTS,
                           page_size=PAGE_SIZE, prefill_chunk=PREFILL_CHUNK,
                           max_seq=PROMPT_LEN + MAX_NEW)
        eng = ContinuousEngine(cfg, params, scfg)
        reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=MAX_NEW)
                for i in range(SERVE_REQUESTS)]
        meter.reset()
        t0 = time.perf_counter()
        outs = eng.serve(reqs)
        wall = time.perf_counter() - t0
        meter.report(name)
        st = eng.stats
        n_tok = sum(len(v) for v in outs.values())
        check(len(outs) == SERVE_REQUESTS, f"{name}: {len(outs)} finished")
        check(all(len(v) == MAX_NEW for v in outs.values()),
              f"{name}: short outputs")
        check(all(0 <= int(t) < cfg.vocab for v in outs.values() for t in v),
              f"{name}: token out of range")
        check((st["decode_traces"], st["prefill_traces"]) == (1, 1),
              f"{name}: traces {st['decode_traces']}/{st['prefill_traces']}")
        log(f"{name}: {len(outs)}/{SERVE_REQUESTS} requests, {n_tok} tokens, "
            f"decode_ticks={st['decode_ticks']} "
            f"prefill_chunks={st['prefill_chunks']} "
            f"peak_pages={st['peak_pages']}/{st['num_pages']} "
            f"traces={st['decode_traces']}/{st['prefill_traces']} "
            f"nonfinite_terminated={eng.nonfinite_terminated}")
        log(f"{name}: smoke timing, not a benchmark: serve() wall "
            f"{wall:.2f} s (compiles included)")
        memory(name, [dev])
        decode_tick_check(eng, name, ("flash_decode", "junction_fwd_int8"
                                      if quant else "junction_fwd"))
        del eng
        gc.collect()


# ------------------------------------------------------------------ sweep
def phase_sweep(meter, dev):
    import jax.numpy as jnp
    from repro.launch import sweep

    name = "phase 4 sweep"
    meter.reset()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp:
        result = sweep.main(["--engine", "pallas", "--rounds", "2",
                             "--steps-per-round", "2",
                             "--out", str(Path(tmp) / "SWEEP_smoke.json")])
    wall = time.perf_counter() - t0
    meter.report(name)
    sizes = [len(st.cohort.specs) for st in result.states]
    check(sizes and all(n == 3 for n in sizes), f"{name}: cohorts {sizes}")
    w = result.ledger.winner()
    check(w is not None and w.eval_losses
          and math.isfinite(w.eval_losses[-1]), f"{name}: no finite winner")
    st = result.states[0]
    xb = jnp.zeros((128, st.cohort.specs[0].layers[0]))
    compiled = st.step.lower(st.params, st.mom, st.hyp, st.mask, xb,
                             st.t_train_pad[:128]).compile()
    compiled_text(compiled, name, ("junction_update_dw",))
    log(f"{name}: cohorts of E={sizes}, winner density="
        f"{w.config['density']} lr={w.config['lr']} "
        f"eval_loss={w.eval_losses[-1]}")
    log(f"{name}: smoke timing, not a benchmark: sweep wall {wall:.2f} s "
        "(compiles included)")
    memory(name, [dev])


# -------------------------------------------------------------- 4 chips
def phase_data_parallel(meter, devices):
    """launch/train.py --data 4: phase 1's step sharded over four chips,
    then the same step on one chip, on the same params and batch."""
    import jax
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import shard_train_step
    from repro.train.steps import make_train_step

    cfg = model_config()
    opt = train_optimizer()
    kernels = ("junction_fwd", "junction_dw", "junction_dx")

    params = init_params(cfg)
    step, params, opt_state = shard_train_step(
        cfg, make_train_step(cfg, opt, jit=False), params, opt.init(params),
        make_local_mesh(len(devices), 1))
    gc.collect()
    memory("data=4 state placed", devices)
    dp, p0, p1 = train_run("data=4", cfg, step, params, opt_state, meter,
                           kernels, 2)
    peaks = memory("data=4", devices)
    check(all(peaks), f"data=4: a device held nothing: {peaks}")
    del step, params, opt_state
    gc.collect()

    params = init_params(cfg)
    one, q0, q1 = train_run("one chip", cfg, make_train_step(cfg, opt),
                            params, opt.init(params), meter, kernels, 2)
    losses_agree("data=4 vs one chip, first loss", dp[:1], one[:1])
    losses_agree("data=4 vs one chip, after the update", dp[1:], one[1:],
                 DRIFT_RTOL)
    update_gap("data=4 vs one chip", p0, p1, q0, q1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    check(ops.resolve_engine("auto") == "pallas"
          and ops.resolve_engine("pallas") == "pallas",
          "engine does not resolve to the Pallas kernels")
    check(not ops._auto_interpret(), "kernels would run in interpret mode")
    log(f"device {devices[0].device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, compile cache {enable_compile_cache()}")
    cfg = model_config()
    log(f"model {ARCH}: d_model {cfg.d_model}, heads {cfg.n_heads}x"
        f"{cfg.head_dim} (kv {cfg.kv_heads}), d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}, sparse FFN density 0.25 block 128; cut: n_layers "
        f"32 -> {LAYERS}")
    meter = CompileMeter()
    if args.chips == 4:
        phase_data_parallel(meter, devices[:4])
    else:
        phase_train(meter, devices[0])
        phase_serve(meter, devices[0])
        phase_sweep(meter, devices[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
