"""Population-sweep driver: a hyperparameter grid on MNIST, end to end.

    PYTHONPATH=src python -m repro.launch.sweep \
        --densities 0.25,0.5 --lrs 0.02,0.05,0.1 --rounds 3 \
        --steps-per-round 20 --out SWEEP_mnist.json

The default grid is density x lr under SGD; ``--optim adam`` switches
every member to the in-kernel Adam epilogue and opens the ``--b1s`` /
``--wds`` axes (grid = density x lr x b1 x wd, with per-member rows in
the ``[E, HYP_K]`` hyp table).  One optimizer kind per sweep — the
accumulator-slot layout is structural.

Builds the candidate grid, buckets it into same-structure cohorts
(candidates sharing a quantized fan-in train as ONE E-batched
population), runs successive halving (search/scheduler.py), and writes
the lineage ledger JSON — per-member config, loss curves, rounds
survived, and the winning configuration.  ``--tag`` stamps the artifact
meta exactly like ``benchmarks/run.py --tag`` stamps BENCH_*.json.
"""
from __future__ import annotations

import argparse


def _floats(s: str) -> list[float]:
    return [float(v) for v in s.split(",") if v]


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--densities", default="0.25,0.5", metavar="D1,D2,...")
    ap.add_argument("--lrs", default="0.02,0.05,0.1", metavar="L1,L2,...")
    ap.add_argument("--momentum", type=float, default=0.0)
    ap.add_argument("--optim", choices=("sgd", "adam"), default="sgd",
                    help="per-member update rule (one kind per sweep: the "
                         "slot layout is structural)")
    ap.add_argument("--b1s", default="0.9", metavar="B1,B2,...",
                    help="Adam b1 sweep axis (--optim adam only)")
    ap.add_argument("--wds", default="0.0", metavar="W1,W2,...",
                    help="Adam weight-decay sweep axis (--optim adam only)")
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--steps-per-round", type=int, default=20)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--samples", type=int, default=4096,
                    help="train samples drawn from the MNIST epoch")
    ap.add_argument("--eval-samples", type=int, default=512)
    ap.add_argument("--engine", default="auto",
                    help="pallas | jnp | auto (fused BP+UP on pallas)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tag", default="sweep",
                    help="artifact meta tag (ledger meta.tag)")
    ap.add_argument("--out", default="SWEEP_mnist.json")
    ap.add_argument("--obs", default=None, metavar="PATH",
                    help="flight-recorder JSONL sink: rank/prune/"
                         "quarantine round events and the host time of "
                         "each scheduler span; render with "
                         "repro.launch.obs_report")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the sweep in a jax.profiler trace written "
                         "to DIR (kernels show up named by KernelSpec)")
    return ap.parse_args(argv)


def main(argv=None):
    args = _parse(argv)
    import numpy as np

    from repro.configs.base import SweepConfig
    from repro.data.mnist import paper_dataset
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import Recorder, profile_ctx
    from repro.search import CandidateSpec, bucket, run_sweep

    enable_compile_cache()
    # output width = smallest block multiple holding the 32 padded classes
    out_w = -(-32 // args.block) * args.block
    layers = (1024, args.hidden, out_w)
    if args.optim == "adam":
        # adam grid: density x lr x b1 x wd (momentum field carries b1)
        grid = [(d, lr, b1, wd)
                for d in _floats(args.densities)
                for lr in _floats(args.lrs)
                for b1 in _floats(args.b1s)
                for wd in _floats(args.wds)]
        specs = [CandidateSpec(lr=lr, momentum=b1, opt="adam",
                               weight_decay=wd, density=d,
                               layers=layers, block=args.block,
                               init_seed=i)
                 for i, (d, lr, b1, wd) in enumerate(grid)]
    else:
        specs = [CandidateSpec(lr=lr, momentum=args.momentum, density=d,
                               layers=layers, block=args.block,
                               init_seed=i)
                 for i, (d, lr) in enumerate(
                     (d, lr) for d in _floats(args.densities)
                     for lr in _floats(args.lrs))]

    n = args.samples + args.eval_samples
    x, t, _ = paper_dataset(n=n, seed=args.seed)
    x_train, t_train = x[:args.samples], t[:args.samples]
    x_eval, t_eval = x[args.samples:], t[args.samples:]

    cfg = SweepConfig(rounds=args.rounds,
                      steps_per_round=args.steps_per_round,
                      batch_size=args.batch,
                      eval_samples=args.eval_samples,
                      seed=args.seed, engine=args.engine)
    n_cohorts = len(bucket(specs))
    # resolved ONCE, same rule as search.population.make_population_step:
    # the in-kernel per-member update needs the pallas engine
    from repro.core.sparse_linear import resolve_engine
    eng = resolve_engine(cfg.engine)
    path = ("fused BP+UP" if cfg.fused and eng == "pallas"
            else "two-pass (materialized grads)")
    print(f"[sweep] {len(specs)} candidates in {n_cohorts} cohort(s), "
          f"{cfg.rounds} rounds x {cfg.steps_per_round} steps, "
          f"engine={eng}")
    print(f"[sweep] optim={args.optim} update path: {path}")
    recorder = (Recorder(args.obs, meta={"launcher": "sweep",
                                         "tag": args.tag})
                if args.obs else None)
    try:
        with profile_ctx(args.profile):
            result = run_sweep(specs, x_train, t_train, x_eval, t_eval, cfg,
                               tag=args.tag, recorder=recorder)
    finally:
        if recorder is not None:
            recorder.close()
            print(f"[sweep] telemetry -> {args.obs} "
                  f"({recorder.n_events} events)")
    led = result.ledger
    led.save(args.out)
    print(f"[sweep] traces: step {led.meta['step_traces']}, eval "
          f"{led.meta['eval_traces']}, programs reused "
          f"{led.meta['programs_reused']} ({n_cohorts} cohort(s))")

    for m in sorted(led.members, key=lambda m: (m.pruned_at is None,
                                                m.rounds_survived)):
        ev = f"{m.eval_losses[-1]:.5f}" if m.eval_losses else "-"
        status = ("WINNER" if m.winner else
                  "live" if m.pruned_at is None else
                  f"quarantined@r{m.quarantined_at['round']}"
                  if m.quarantined_at is not None else
                  f"pruned@r{m.pruned_at}")
        hyps = f"density={m.config['density']} lr={m.config['lr']}"
        if m.config.get("opt") == "adam":
            hyps += (f" b1={m.config['momentum']} "
                     f"wd={m.config['weight_decay']}")
        print(f"[sweep]   member {m.member}: {hyps} eval={ev} {status}")
    w = led.winner()
    if w is None:
        import math
        survived = [m for m in led.members
                    if m.pruned_at is None and m.eval_losses]
        if survived and all(not math.isfinite(m.eval_losses[-1])
                            for m in survived):
            raise SystemExit("[sweep] no winner: every surviving candidate "
                             "diverged (non-finite eval loss) — lower the "
                             "lr grid")
        raise SystemExit("[sweep] no winner — sweep ran no rounds?")
    whyp = f"density={w.config['density']} lr={w.config['lr']}"
    if w.config.get("opt") == "adam":
        whyp += f" b1={w.config['momentum']} wd={w.config['weight_decay']}"
    print(f"[sweep] winner: {whyp} "
          f"eval_loss={w.eval_losses[-1]:.5f} -> {args.out}")
    return result


if __name__ == "__main__":
    main()
