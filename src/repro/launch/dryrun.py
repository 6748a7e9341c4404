import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as its own process (``python -m repro.launch.dryrun ...``) — the
first two lines above force 512 host-platform devices BEFORE jax
initializes.  Tests and benchmarks never import this module.

Per cell:
  * build the production mesh (16,16) or (2,16,16),
  * abstract-init params/optimizer/cache (ShapeDtypeStruct, no allocation),
  * attach NamedShardings from parallel/sharding.py,
  * jit(...).lower(...).compile(),
  * record memory_analysis / cost_analysis / roofline walker output as JSON.

Results land in ``results/dryrun/<cell>.json`` and are skipped when present
(crash-safe sweep; delete a file to redo a cell).
"""

import argparse
import dataclasses
import functools
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.base import SHAPES, ArchConfig, ShapeSpec, valid_cells
from repro.core.sparsity import SparsityConfig
from repro.launch import specs as specs_mod
from repro.launch.mesh import make_production_mesh
from repro.parallel import hints
from repro.models import model as M
from repro.optim import adam, constant_schedule
from repro.parallel import sharding as sh
from repro.roofline import analysis as roofline
from repro.train.steps import make_decode_step, make_prefill_step, make_train_step

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# sweep order: small archs first so results accumulate fast
SWEEP_ORDER = [
    "whisper-base", "stablelm-3b", "zamba2-2.7b", "deepseek-7b",
    "llava-next-mistral-7b", "falcon-mamba-7b", "deepseek-v2-lite-16b",
    "qwen3-moe-30b-a3b", "qwen2-72b", "command-r-plus-104b",
]


def cell_id(arch: str, shape: str, mesh_kind: str, variant: str) -> str:
    v = "" if variant == "dense" else f"+{variant}"
    return f"{arch}{v}__{shape}__{mesh_kind}"


def _apply_variant(cfg: ArchConfig, variant: str) -> ArchConfig:
    import dataclasses
    if variant == "dense":
        return cfg
    if variant == "sparse":   # the paper's technique on FFN projections
        return cfg.with_sparsity(SparsityConfig(density=0.125, block=128,
                                                where="ffn"))
    if variant == "sparse-all":
        return cfg.with_sparsity(SparsityConfig(density=0.125, block=128,
                                                where="ffn+attn"))
    if variant == "perf":     # beyond-paper knobs (§Perf): bf16-resident
        # params (fp32 masters in adam -> bf16 FSDP gathers) + chunked CE
        # (logits never fully materialize) + bf16 selective-scan elements
        # (ssm_chunk=16 tried and REFUTED — carry r/w per chunk dominates at
        # small chunks, t_m 104 -> 258 s; see EXPERIMENTS.md §Perf F2)
        return dataclasses.replace(cfg, param_dtype="bfloat16",
                                   loss_chunk=2048,
                                   ssm_scan_dtype="bfloat16")
    if variant == "perf-sparse":
        return dataclasses.replace(
            cfg.with_sparsity(SparsityConfig(density=0.125, block=128,
                                             where="ffn")),
            param_dtype="bfloat16", loss_chunk=2048,
            ssm_scan_dtype="bfloat16")
    raise ValueError(variant)


def lower_cell(cfg: ArchConfig, shape: ShapeSpec, mesh, microbatches: int = 1):
    """Returns the lowered computation for one cell."""
    pshapes = jax.eval_shape(functools.partial(M.init, cfg),
                             jax.random.PRNGKey(0))
    pspecs = sh.param_specs(cfg, pshapes, mesh)
    pstruct = sh.attach(pshapes, pspecs, mesh)

    if shape.kind == "train":
        opt = adam(constant_schedule(1e-4),
                   master_copy=(cfg.param_dtype != "float32"))
        oshapes = jax.eval_shape(opt.init, pshapes)
        ospecs = sh.opt_state_specs(oshapes, pspecs)
        ostruct = sh.attach(oshapes, ospecs, mesh)
        batch = specs_mod.batch_struct(cfg, shape)
        bspecs = sh.batch_specs(cfg, batch, mesh)
        bstruct = sh.attach(batch, bspecs, mesh)
        step = jax.ShapeDtypeStruct((), jnp.int32)
        fn = make_train_step(cfg, opt, microbatches=microbatches, jit=False)
        jitted = jax.jit(fn, donate_argnums=(0, 1),
                         out_shardings=(sh.to_shardings(pspecs, mesh),
                                        sh.to_shardings(ospecs, mesh), None))
        return jitted.lower(pstruct, ostruct, bstruct, step)

    if shape.kind == "prefill":
        batch = specs_mod.batch_struct(cfg, shape)
        bstruct = sh.attach(batch, sh.batch_specs(cfg, batch, mesh), mesh)
        cshapes = jax.eval_shape(
            lambda: M.make_cache(cfg, shape.global_batch, shape.seq_len))
        cspecs = sh.cache_specs(cfg, cshapes, mesh)
        lspec = sh.logits_spec(cfg, shape.global_batch, mesh)
        fn = make_prefill_step(cfg)
        jitted = jax.jit(fn, out_shardings=(
            sh.to_shardings(lspec, mesh), sh.to_shardings(cspecs, mesh)))
        return jitted.lower(pstruct, bstruct)

    # decode
    cshapes = jax.eval_shape(
        lambda: M.make_cache(cfg, shape.global_batch, shape.seq_len))
    cspecs = sh.cache_specs(cfg, cshapes, mesh)
    cstruct = sh.attach(cshapes, cspecs, mesh)
    tok, pos = specs_mod.decode_inputs_struct(cfg, shape)
    tspec = sh.batch_specs(cfg, tok, mesh)
    tstruct = sh.attach(tok, tspec, mesh)
    lspec = sh.logits_spec(cfg, shape.global_batch, mesh)
    fn = make_decode_step(cfg)
    jitted = jax.jit(fn, donate_argnums=(1,), out_shardings=(
        sh.to_shardings(lspec, mesh), sh.to_shardings(cspecs, mesh)))
    return jitted.lower(pstruct, cstruct, tstruct, pos)


def run_cell(arch: str, shape_name: str, mesh_kind: str, variant: str,
             out_dir: Path, force: bool = False) -> dict:
    cid = cell_id(arch, shape_name, mesh_kind, variant)
    out_path = out_dir / f"{cid}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    # force the jnp gather+einsum path: the dry-run exists for FLOP/bytes
    # accounting, which must see the density-scaled einsums, not opaque
    # pallas_call ops the roofline walker can't cost
    cfg = dataclasses.replace(_apply_variant(registry.get(arch), variant),
                              engine="jnp")
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size
    rec: dict = {"cell": cid, "arch": arch, "shape": shape_name,
                 "mesh": mesh_kind, "variant": variant,
                 "n_chips": int(n_chips), "params": cfg.param_count(),
                 "active_params": cfg.active_param_count()}
    t0 = time.time()
    try:
        # training cells auto-scale microbatches (gradient accumulation)
        # until the per-device footprint fits a v5e's 16 GiB
        mb_plan = [1, 2, 4, 8] if shape.kind == "train" else [1]
        attempts = []
        for mb in mb_plan:
            if mb > 1 and shape.global_batch % mb:
                continue
            t0 = time.time()
            with mesh, hints.use_mesh_hints(mesh):
                lowered = lower_cell(cfg, shape, mesh, microbatches=mb)
                rec["lower_s"] = round(time.time() - t0, 1)
                t1 = time.time()
                compiled = lowered.compile()
                rec["compile_s"] = round(time.time() - t1, 1)
            rl = roofline.analyze_compiled(compiled)
            mem = rl.memory_stats
            per_dev_gb = (mem.get("argument_bytes", 0) + mem.get("temp_bytes", 0)
                          + mem.get("output_bytes", 0)
                          - mem.get("alias_bytes", 0)) / 2**30
            # corrected: minus the XLA-CPU f32 loop-widening artifact
            # (roofline/analysis.py::widened_f32_loop_state)
            corr_gb = per_dev_gb - rl.spurious_f32_bytes / 2**30
            attempts.append({"microbatches": mb,
                             "per_device_gb": round(per_dev_gb, 3),
                             "corrected_gb": round(corr_gb, 3)})
            rec["microbatches"] = mb
            if corr_gb < 16.0 or mb == mb_plan[-1]:
                break
        rec["fit_attempts"] = attempts
        rec["roofline"] = rl.to_json()
        rec["model_flops"] = roofline.model_flops(cfg, shape)
        rec["useful_fraction"] = roofline.useful_fraction(
            cfg, shape, rl.dot_flops, n_chips)
        rec["per_device_gb"] = round(per_dev_gb, 3)
        rec["per_device_gb_corrected"] = round(corr_gb, 3)
        rec["fits_16gb"] = corr_gb < 16.0
        rec["ok"] = True
        print(f"[dryrun] {cid}: ok lower={rec['lower_s']}s "
              f"compile={rec['compile_s']}s perdev={per_dev_gb:.2f}GiB "
              f"mb={rec.get('microbatches',1)} "
              f"dom={rec['roofline']['dominant']}", flush=True)
    except Exception as e:  # record failure — these are bugs to fix
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] {cid}: FAIL {rec['error'][:200]}", flush=True)
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1, default=float))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="dense",
                    choices=["dense", "sparse", "sparse-all", "perf",
                             "perf-sparse"])
    ap.add_argument("--out", default=str(RESULTS))
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    out_dir = Path(args.out)

    archs = [args.arch] if args.arch else SWEEP_ORDER
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    n_ok = n_fail = 0
    for arch in archs:
        cfg = registry.get(arch)
        cells = ([SHAPES[args.shape]] if args.shape
                 else list(valid_cells(cfg)))
        for shape in cells:
            for mk in meshes:
                rec = run_cell(arch, shape.name, mk, args.variant, out_dir,
                               force=args.force)
                n_ok += rec.get("ok", False)
                n_fail += not rec.get("ok", False)
    print(f"[dryrun] done: {n_ok} ok, {n_fail} failed", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
