"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b --reduce \
        --steps 200 --batch 8 --seq 256 --sparse --ckpt /tmp/run1

Assembles config -> params -> sharded jit train_step -> restartable data
pipeline -> fault-tolerant loop.  ``--devices N`` forces N host devices for
local multi-device runs (must be first — device count locks at jax init,
which is why this flag is parsed before importing jax).
"""
from __future__ import annotations

import argparse
import os
import sys


def _parse():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--reduce", action="store_true",
                    help="use the reduced (smoke-size) config")
    ap.add_argument("--width", type=int, default=0,
                    help="override d_model (custom scale, e.g. ~100M runs)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optim", choices=("sgd", "adam"), default="adam",
                    help="fused-capable optimizer: fused_sgd(momentum=0.9) "
                         "or fused_adam (two-pass adam reference when the "
                         "config is ineligible)")
    ap.add_argument("--sparse", action="store_true",
                    help="enable the paper's pre-defined sparsity on FFNs")
    ap.add_argument("--density", type=float, default=0.25)
    ap.add_argument("--experts-held", default="",
                    metavar="N[@FIRST]",
                    help="MoE: compute only N of the router's experts "
                         "(from index FIRST, default 0), one chip's share "
                         "of an expert-parallel layer; nothing is dropped")
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--data", type=int, default=1, help="data-parallel size")
    ap.add_argument("--model", type=int, default=1, help="model-parallel size")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a crash at this step (restart test)")
    ap.add_argument("--obs", default=None, metavar="PATH",
                    help="flight-recorder JSONL sink (obs/telemetry.py): "
                         "per-step records + guardian/checkpoint events; "
                         "render with repro.launch.obs_report")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the run in a jax.profiler trace written to "
                         "DIR (kernels show up named by KernelSpec)")
    return ap.parse_args()


def shard_train_step(cfg, step_fn, params, opt_state, mesh):
    """The data/model-parallel form of a raw train step on ``mesh``:
    params and optimizer state are placed by the parallel/sharding.py
    rules, the batch is split over the data axis, and the activation
    hints are active while the step traces.  Returns
    ``(train_step, params, opt_state)`` — the step jitted with params and
    state donated, and both trees placed on the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.parallel import hints
    from repro.parallel import sharding as sh

    pspecs = sh.param_specs(cfg, params, mesh)
    psh = sh.to_shardings(pspecs, mesh)
    osh = sh.to_shardings(sh.opt_state_specs(opt_state, pspecs), mesh)
    bsh = NamedSharding(mesh, P(sh.dp_axes(mesh)))

    def step(params, opt_state, batch, step):
        with hints.use_mesh_hints(mesh):
            return step_fn(params, opt_state, batch, step)

    train_step = jax.jit(step, donate_argnums=(0, 1),
                         in_shardings=(psh, osh, bsh, None),
                         out_shardings=(psh, osh, None))
    return (train_step, jax.device_put(params, psh),
            jax.device_put(opt_state, osh))


def main():
    args = _parse()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.core.sparsity import SparsityConfig
    from repro.data.pipeline import LMTokenPipeline
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_local_mesh
    from repro.models import model as M
    from repro.obs import Recorder, profile_ctx
    from repro.optim import cosine_schedule, fused_adam, fused_sgd
    from repro.train import grad_compress
    from repro.train.steps import fused_update_eligible, make_train_step
    from repro.train.train_loop import TrainLoopConfig, run

    enable_compile_cache()
    cfg = registry.get(args.arch)
    if args.reduce:
        cfg = cfg.reduced()
    if args.width:
        cfg = dataclasses.replace(cfg, d_model=args.width,
                                  d_ff=args.width * 3,
                                  head_dim=args.width // max(1, cfg.n_heads))
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    if args.sparse:
        block = 32 if args.reduce else 128
        cfg = cfg.with_sparsity(SparsityConfig(density=args.density,
                                               block=block, where="ffn"))
    if args.experts_held:
        if cfg.moe is None:
            raise SystemExit(f"--experts-held: {cfg.name} has no experts")
        n, _, first = args.experts_held.partition("@")
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=int(n), first_held=int(first or 0)))

    sched = cosine_schedule(args.lr, warmup=20, total=args.steps)
    if args.optim == "sgd":
        opt = fused_sgd(sched, momentum=0.9)
    else:
        opt = fused_adam(sched, grad_clip=1.0)
    if args.compress_grads:
        opt = grad_compress.compressed(opt)

    # resolved ONCE at step build — say which path we're on (and why not,
    # when the fused BP+UP refuses) so runs are attributable
    ok, why = fused_update_eligible(cfg, opt, args.microbatches)
    print(f"[train] optim={args.optim} update path: "
          f"{'fused BP+UP' if ok else f'two-pass ({why})'}")
    # quantization is inference-only (core/quantize.py): training always
    # runs full-precision weights — state the datapath like the fused log
    print("[train] quantize=off datapath: full precision "
          "(int8/fxp junctions are inference-only — see launch/serve.py)")

    params = M.init(cfg, jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    # raw fn: the mesh/sharding branch below attaches its own jit+donation
    step_fn = make_train_step(cfg, opt, microbatches=args.microbatches,
                              jit=False)

    if args.data * args.model > 1:
        mesh = make_local_mesh(args.data, args.model)
        train_step, params, opt_state = shard_train_step(
            cfg, step_fn, params, opt_state, mesh)
    else:
        train_step = jax.jit(step_fn, donate_argnums=(0, 1))

    pipeline = LMTokenPipeline(cfg, args.batch, args.seq)
    loop_cfg = TrainLoopConfig(total_steps=args.steps, ckpt_dir=args.ckpt,
                               ckpt_every=args.ckpt_every,
                               fail_at_step=args.fail_at)
    recorder = (Recorder(args.obs, meta={"launcher": "train",
                                         "arch": args.arch})
                if args.obs else None)
    try:
        with profile_ctx(args.profile):
            result = run(loop_cfg, train_step, params, opt_state, pipeline,
                         recorder=recorder)
    finally:
        if recorder is not None:
            recorder.close()
            print(f"[train] telemetry -> {args.obs} "
                  f"({recorder.n_events} events)")
    print(f"[train] finished at step {result['step']}; "
          f"stragglers={result['straggler_count']}")
    if result["history"]:
        print(f"[train] first loss {result['history'][0]['loss']:.4f} "
              f"-> last {result['history'][-1]['loss']:.4f}")
    if result["moe"]:
        m = result["moe"]
        pad = m["moe_computed_rows"] - m["moe_routed_rows"]
        print(f"[train] moe: routed {m['moe_routed_rows']} rows, computed "
              f"{m['moe_computed_rows']} ({pad} padding), largest expert "
              f"{m['moe_max_expert_rows']}, dropped {m['moe_dropped_rows']}")
    return result


if __name__ == "__main__":
    main()
