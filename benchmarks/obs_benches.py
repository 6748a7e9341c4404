"""Telemetry-overhead benchmark (ISSUE 10): the flight recorder on vs off.

``bench.obs.overhead`` times the two hot producer paths with a real
Recorder (JSONL sink on disk, events + histograms live) against
the identical run with no recorder:

* the guardian-instrumented regression train loop (train/train_loop.py —
  per-step TrainStep events, the guardian's host-side sentinel checks
  riding along), and
* a continuous-serve trace (serve/engine.ContinuousEngine — per-request
  spans, TTFT/ITL observations, the scheduler spans' durations).

``us_per_call`` is the recorder-ON wall time; ``derived`` carries the
per-path and overall on/off ratios — the acceptance gate's number.  By
the no-extra-device-sync contract the recorder adds only host dict/deque
work and one json line per event, so the ratio should sit near 1.0; a
regression here means someone put device work (or a sync) on the
telemetry path.
"""
from __future__ import annotations

import time


def bench(fast=True):
    import tempfile

    import jax
    import numpy as np

    from repro.configs.base import ArchConfig
    from repro.core.sparsity import SparsityConfig
    from repro.models import model as M
    from repro.obs import Recorder
    from repro.serve.engine import ContinuousEngine, Request, ServeConfig

    tmp = tempfile.mkdtemp(prefix="obs_bench_")

    # ---- train path: guardian loop on the MNIST-sized regression step
    import sys
    sys.path.insert(0, "tests")     # reuse the guardian e2e fixtures
    try:
        from test_guardian import (PoisonPipeline, _junction,
                                   _make_regression_step, _w_true)
    finally:
        sys.path.pop(0)
    from repro.train.train_loop import (GuardianConfig, TrainLoopConfig,
                                        run)

    w_true = _w_true()
    params = _junction()
    opt, train_step = _make_regression_step("jnp")
    STEPS = 12 if fast else 60

    def train_pass(recorder, tag):
        cfg = TrainLoopConfig(total_steps=STEPS,
                              ckpt_dir=f"{tmp}/ck_{tag}",
                              ckpt_every=10 ** 6, log_every=10 ** 6,
                              guardian=GuardianConfig())
        t0 = time.perf_counter()
        run(cfg, train_step, params, opt.init(params),
            PoisonPipeline(w_true), log=lambda s: None, recorder=recorder)
        return time.perf_counter() - t0

    train_pass(None, "warm")                    # compile excluded
    dt_train_off = train_pass(None, "off")
    rec = Recorder(f"{tmp}/train.jsonl")
    dt_train_on = train_pass(rec, "on")
    rec.close()

    # ---- serve path: a continuous trace with spans and histograms live
    cfg = ArchConfig(
        name="bench-obs", family="dense", n_layers=2, d_model=128,
        n_heads=4, kv_heads=2, head_dim=32, d_ff=256, vocab=128,
        act="silu", max_seq=64, attn_chunk=32, dtype="float32",
        sparsity=SparsityConfig(density=0.25, block=32, where="ffn"),
        engine="jnp")
    mparams = M.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    n_req = 8 if fast else 24
    NEW = 8
    prompts = rng.integers(1, cfg.vocab, size=(n_req, 12)).astype(np.int32)
    scfg = ServeConfig(max_new_tokens=NEW, eos_token=-1, slots=2,
                       page_size=8, prefill_chunk=8, max_seq=32)
    reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=NEW)
            for i in range(n_req)]

    def serve_pass(recorder):
        eng = ContinuousEngine(cfg, mparams, scfg, recorder=recorder)
        eng.serve(list(reqs))                   # warmup pass (compiles)
        t0 = time.perf_counter()
        eng.serve(list(reqs))
        return time.perf_counter() - t0

    dt_serve_off = serve_pass(None)
    rec = Recorder(f"{tmp}/serve.jsonl")
    dt_serve_on = serve_pass(rec)
    rec.close()

    r_train = dt_train_on / max(dt_train_off, 1e-12)
    r_serve = dt_serve_on / max(dt_serve_off, 1e-12)
    r_all = ((dt_train_on + dt_serve_on)
             / max(dt_train_off + dt_serve_off, 1e-12))
    return [{
        "name": "bench.obs.overhead",
        "us_per_call": (dt_train_on + dt_serve_on) * 1e6,
        "derived": f"train {STEPS} steps + serve {n_req} reqs x {NEW} tok "
                   f"recorder on/off: train_ratio={r_train:.3f} "
                   f"serve_ratio={r_serve:.3f} ratio={r_all:.3f}",
    }]
