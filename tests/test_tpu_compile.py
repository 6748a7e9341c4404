"""The main-path Pallas kernels, and the paged serve tick and prefill
chunk, compile for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described (not
attached) ``v5e:2x2`` topology, and refuses what the chip would refuse —
block shapes off the (8, 128) tiling, scalar stores to VMEM, unaligned
lane slices — which interpret mode on the CPU never sees.  Shapes are
stablelm-3b's FFN junction (2560 -> 6912 at density 0.25, block 128) and
its KV pages (32 heads x head_dim 80), plus a head_dim-128 GQA decode.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and only the worker that runs
this file should.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.core.sparsity import make_block_pattern
from repro.kernels import block_sparse_matmul as bsm
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.models import model as M
from repro.serve.engine import ContinuousEngine, ServeConfig

D_MODEL, D_FF, BLOCK, ROWS = 2560, 6912, 128, 1024
PAT = make_block_pattern(D_MODEL, D_FF, 0.25, BLOCK)
NOB, KB = PAT.idx.shape


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    log_dir = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the cache but cannot be
    # read back without one: keep the cache out of these compiles
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir == "disabled":
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def chip(topo):
    """ShapeDtypeStruct factory on one described v5e chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


def _compiled(fn, *args, kernel: str | None) -> str:
    # the program's own matmul precision: the suite's "highest" default
    # (conftest.py) would ask the MXU for an fp32 contraction of bf16 tiles
    fn = fn if hasattr(fn, "lower") else jax.jit(fn)
    with jax.default_matmul_precision("default"):
        txt = fn.lower(*args).compile().as_text()
    if kernel is not None:
        assert "tpu_custom_call" in txt
        assert kernel in txt
    return txt


def _junction_operands(chip, E):
    x = chip((E, ROWS, D_MODEL))
    w = chip((E, NOB, KB, BLOCK, BLOCK))
    dy = chip((E, ROWS, D_FF))
    idx = chip(PAT.idx.shape, jnp.int32)
    return x, w, dy, idx


@pytest.mark.parametrize("E", [1, 4])
def test_fwd_with_bias_compiles(chip, E):
    x, w, _, idx = _junction_operands(chip, E)
    _compiled(lambda x, w, i, b: bsm.fwd(x, w, i, b, act="sigmoid")[0],
              x, w, idx, chip((E, D_FF)), kernel="junction_fwd")


def test_gated_fwd_compiles(chip):
    x, w, _, idx = _junction_operands(chip, 1)
    _compiled(lambda x, w, i: bsm.gated_fwd(x, w, w, i, save_res=True),
              x, w, idx, kernel="junction_gated_fwd")


def test_dx_compiles(chip):
    _, w, dy, _ = _junction_operands(chip, 1)
    rev = [chip(a.shape, jnp.int32)
           for a in (PAT.rev_ob, PAT.rev_t, PAT.rev_cnt)]
    _compiled(lambda dy, w, ro, rt, rc: bsm.dx(dy, w, ro, rt, rc, dy,
                                               act="silu"),
              dy, w, *rev, kernel="junction_dx")


def test_dw_with_bias_compiles(chip):
    x, _, dy, idx = _junction_operands(chip, 1)
    _compiled(lambda x, dy, i: bsm.dw(x, dy, i, dy, act="silu"),
              x, dy, idx, kernel="junction_dw")


def test_fwd_int8_compiles(chip):
    x, _, _, idx = _junction_operands(chip, 1)
    _compiled(lambda x, w, i, s, b: bsm.fwd_int8(x, w, i, s, b),
              x, chip((1, NOB, KB, BLOCK, BLOCK), jnp.int8), idx,
              chip((1, NOB, KB), jnp.float32), chip((1, D_FF), jnp.float32),
              kernel="junction_fwd_int8")


@pytest.mark.parametrize("E", [1, 4])
def test_update_dw_with_health_compiles(chip, E):
    """Adam with a bias: every per-unit operand (bias and its m/v slots,
    the health flags) is an [E, ...] array the chip must tile."""
    x, w, dy, idx = _junction_operands(chip, E)
    slot = chip((E, NOB, KB, BLOCK, BLOCK), jnp.float32)
    b, b_slot = chip((E, D_FF)), chip((E, D_FF), jnp.float32)

    def step(x, dy, i, w, b, m, mb, hyp):
        return bsm.update_dw(x, dy, i, dy, w, b, m, mb, hyp, vel=m,
                             vel_b=mb, act="sigmoid", with_health=True)

    _compiled(step, x, dy, idx, w, b, slot, b_slot,
              chip((E, bsm.HYP_K), jnp.float32), kernel="junction_update_dw")


@pytest.mark.parametrize("E", [1, 4])
def test_update_gated_dw_with_health_compiles(chip, E):
    x, w, dy, idx = _junction_operands(chip, E)
    slot = chip((E, NOB, KB, BLOCK, BLOCK), jnp.float32)

    def step(x, dy, i, w, m, hyp):
        return bsm.update_gated_dw(x, dy, i, dy, dy, w, w, m, m, hyp, vg=m,
                                   vi=m, with_health=True)

    _compiled(step, x, dy, idx, w, slot, chip((E, bsm.HYP_K), jnp.float32),
              kernel="junction_update_gated_dw")


@pytest.mark.parametrize("slots,hkv,rep,hd,maxp,G", [
    (8, 32, 1, 80, 36, 8), (8, 8, 8, 128, 36, 32),
    (32, 32, 1, 80, 160, 8), (32, 8, 8, 128, 160, 32)],
    ids=["mha_hd80", "gqa_hd128", "serve_cell_mha_hd80", "gqa_hd128_maxp160"])
def test_flash_decode_compiles(chip, slots, hkv, rep, hd, maxp, G):
    """stablelm-3b's head_dim 80 is not a lane multiple: the merged
    [P, ps, Hkv*hd] page layout keeps every DMA and load lane-aligned.
    The serve cell's shapes (32 slots, pages of 16, max_seq 2560) read
    groups of 8 pages a grid step, GQA's narrower rows 32; both grids
    fit the default scoped VMEM."""
    ps = 16
    assert fa.decode_pages_per_step(ps, hkv * hd, 2, maxp) == G
    pool = chip((slots * maxp + 1, ps, hkv * hd))
    _compiled(lambda q, k, v, pt, n: fa.flash_decode(q, k, v, pt, n,
                                                     interpret=False),
              chip((slots, hkv, rep, hd)), pool, pool,
              chip((slots, maxp), jnp.int32), chip((slots,), jnp.int32),
              kernel="flash_decode")


# an HLO instruction: "%name = <result type> <opcode>(" (the type may be a tuple)
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%\S+\s=\s(.*?)\s([a-z][a-z0-9\-]*)\(")


def _instrs_by_size(txt: str, sizes: set[int]) -> list[tuple[str, str]]:
    """(opcode, line) of every instruction, fused ones included, whose
    result holds an array with one of ``sizes`` elements."""
    out = []
    for line in txt.splitlines():
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        for dims in re.findall(r"[a-z][a-z0-9]*\[([\d,]+)\]", m.group(1)):
            n = 1
            for d in dims.split(","):
                n *= int(d)
            if n in sizes:
                out.append((m.group(2), line.strip()[:160]))
                break
    return out


@pytest.mark.parametrize("step", ["tick", "prefill_chunk"])
def test_paged_serve_step_writes_pool_in_place(chip, monkeypatch, step):
    """The engine's decode tick and prefill chunk, pool donated, at
    stablelm-3b's KV widths (32 heads x 80, pages of 16): each layer's new
    rows are scattered into the stacked pool, and no copy, dynamic-slice or
    dynamic-update-slice makes a buffer the size of one layer's pool or of
    the whole pool (the per-layer slice and write-back the flat-pool layer
    scan removed)."""
    monkeypatch.setattr(ops, "_auto_interpret", lambda: False)
    L, slots, ps, C, max_seq = 3, 8, 16, 64, 256
    cfg = dataclasses.replace(
        registry.get("stablelm-3b"), n_layers=L, d_model=256, d_ff=512,
        vocab=256, raw_vocab=256, max_seq=max_seq, engine="pallas",
        param_dtype="bfloat16")
    on_chip = lambda t: jax.tree.map(lambda s: chip(s.shape, s.dtype), t)
    params = on_chip(jax.eval_shape(
        lambda: M.init(cfg, jax.random.PRNGKey(0))))
    eng = ContinuousEngine(cfg, params, ServeConfig(
        slots=slots, page_size=ps, prefill_chunk=C, max_seq=max_seq))
    maxp = eng.pages_per_slot
    P = slots * maxp + 1
    pool = on_chip(jax.eval_shape(lambda: M.make_paged_cache(cfg, P, ps)))
    i32 = jnp.int32
    if step == "tick":
        txt = _compiled(eng._tick, params, pool, chip((slots, 1), i32),
                        chip((slots,), i32), chip((slots, maxp), i32),
                        chip((2,), jnp.uint32), kernel="flash_decode")
    else:
        txt = _compiled(eng._prefill_chunk, params, pool, chip((1, C), i32),
                        chip((), i32), chip((maxp,), i32), chip((), i32),
                        kernel=None)
    layer = P * ps * cfg.kv_heads * cfg.head_dim
    found = _instrs_by_size(txt, {layer, L * layer})
    assert "scatter" in {op for op, _ in found}
    copies = [line for op, line in found
              if op in ("copy", "copy-start", "copy-done", "dynamic-slice",
                        "dynamic-update-slice")]
    assert not copies, "\n".join(copies)


# ------------------------------------------- expert kernels with live counts
# The MoE training cell's expert junctions: E=8 held experts, a buffer of
# 32,768 rows each (4 x 8192 tokens), 2048 -> 1408 at kb 4 (gated) and
# 1408 -> 2048 at kb 3, bf16, the fused Adam epilogue.
EXP, EXP_ROWS, EXP_D, EXP_F = 8, 32768, 2048, 1408
PIN = make_block_pattern(EXP_D, EXP_F, 0.25, BLOCK)
POUT = make_block_pattern(EXP_F, EXP_D, 0.25, BLOCK)

# location-free Mosaic of each kernel called WITHOUT counts at these
# shapes, as the kernels stood before counts were added (sha256, 16 hex)
UNCOUNTED_MOSAIC = {
    "gated_fwd": "489878efb97e32d9", "fwd": "e8b273c02d569e50",
    "gated_dx": "536f74fea442d8a6", "dx": "460cd4438b64dd4b",
    "gated_dw": "6b3a96f44b955e1d", "dw": "552b8c0391e553ba",
    "update_gated_dw": "57e9da81c3c6b445", "update_dw": "389467c8f110dbb8",
}


def _expert_calls(chip, counted: bool):
    """name -> (fn, operands) of every E-batched expert kernel."""
    i32, f32 = jnp.int32, jnp.float32
    x, h, y = (chip((EXP, EXP_ROWS, EXP_D)), chip((EXP, EXP_ROWS, EXP_F)),
               chip((EXP, EXP_ROWS, EXP_D)))
    wg = chip((EXP,) + PIN.idx.shape + (BLOCK, BLOCK))
    wo = chip((EXP,) + POUT.idx.shape + (BLOCK, BLOCK))
    slot = lambda w: chip(w.shape, f32)
    idx_i, idx_o = chip(PIN.idx.shape, i32), chip(POUT.idx.shape, i32)
    rv_i = [chip(a.shape, i32) for a in (PIN.rev_ob, PIN.rev_t, PIN.rev_cnt)]
    rv_o = [chip(a.shape, i32)
            for a in (POUT.rev_ob, POUT.rev_t, POUT.rev_cnt)]
    hyp, b0 = chip((EXP, bsm.HYP_K), f32), chip((EXP, EXP_D))
    tail = (chip((EXP,), i32),) if counted else ()

    def c(fn):   # the counts ride as the last operand when counted
        def call(*a):
            return fn(*a[:len(a) - len(tail)], counts=a[-1] if tail else None)
        return call

    return {
        "gated_fwd": (c(lambda x, a, b, i, counts: bsm.gated_fwd(
            x, a, b, i, save_res=True, counts=counts)),
            (x, wg, wg, idx_i) + tail),
        "fwd": (c(lambda h, w, i, b, counts: bsm.fwd(
            h, w, i, b, counts=counts)[0]), (h, wo, idx_o, b0) + tail),
        "gated_dx": (c(lambda dh, a, b, r0, r1, r2, g, u, counts: bsm.gated_dx(
            dh, a, b, r0, r1, r2, g, u, counts=counts)),
            (h, wg, wg, *rv_i, h, h) + tail),
        "dx": (c(lambda dy, w, r0, r1, r2, counts: bsm.dx(
            dy, w, r0, r1, r2, None, counts=counts)), (y, wo, *rv_o) + tail),
        "gated_dw": (c(lambda x, dh, i, g, u, counts: bsm.gated_dw(
            x, dh, i, g, u, counts=counts)), (x, h, idx_i, h, h) + tail),
        "dw": (c(lambda h, dy, i, counts: bsm.dw(
            h, dy, i, None, with_bias=False, counts=counts)[0]),
            (h, y, idx_o) + tail),
        "update_gated_dw": (c(
            lambda x, dh, i, g, u, a, b, ma, mb, va, vb, hy, counts:
            bsm.update_gated_dw(x, dh, i, g, u, a, b, ma, mb, hy, vg=va,
                                vi=vb, with_health=True,
                                counts=counts)[:7:6]),
            (x, h, idx_i, h, h, wg, wg, slot(wg), slot(wg), slot(wg),
             slot(wg), hyp) + tail),
        "update_dw": (c(lambda h, dy, i, w, m, v, hy, counts: bsm.update_dw(
            h, dy, i, None, w, None, m, None, hy, vel=v, with_bias=False,
            with_health=True, counts=counts)[::6]),
            (h, y, idx_o, wo, slot(wo), slot(wo), hyp) + tail),
    }


@pytest.mark.parametrize("name", sorted(UNCOUNTED_MOSAIC))
def test_counted_expert_kernel_compiles(chip, name):
    """Each expert kernel with live row counts compiles for the chip at the
    MoE cell's shapes, under its ``expert_junction_`` name."""
    fn, args = _expert_calls(chip, counted=True)[name]
    _compiled(fn, *args, kernel=f"expert_junction_{name}")


def _mosaic_fingerprints(txt: str) -> list[str]:
    """sha256 (16 hex) of each Mosaic kernel in a lowered module, with
    its source locations stripped (they move with every edit)."""
    import base64
    import hashlib
    import json
    from jax._src.lib import tpu as tpu_dialect
    from jax._src.lib.mlir import ir
    out = []
    for cfg in re.findall(r'backend_config = "([^"]*)"', txt):
        cfg = json.loads(re.sub(r"\\([0-9A-Fa-f]{2})",
                                lambda g: chr(int(g.group(1), 16)), cfg))
        body = base64.b64decode(cfg["custom_call_config"]["body"])
        with ir.Context() as ctx:
            tpu_dialect.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True
            asm = ir.Module.parse(body).operation.get_asm(
                enable_debug_info=False)
        out.append(hashlib.sha256(asm.encode()).hexdigest()[:16])
    return out


def test_uncounted_expert_kernels_lower_as_before(chip):
    """Called without counts, every expert kernel lowers to the Mosaic it
    lowered to before counts existed: the sweep's and the dense cells'
    kernels are unchanged."""
    got = {}
    for name, (fn, args) in _expert_calls(chip, counted=False).items():
        with jax.default_matmul_precision("default"):
            got[name] = _mosaic_fingerprints(
                jax.jit(fn).lower(*args).as_text())
    assert got == {k: [v] for k, v in UNCOUNTED_MOSAIC.items()}


# ------------------------------------------------- the cells' kernels, pinned
# Every junction kernel configuration the benchmark's five cells launch
# (found by lowering each cell's step for a described v5e and listing its
# kernels), and a few that only tests and the int8 serve path reach, with
# the location-free Mosaic each lowers to.  A key reads
# "kernel ExM n_in>n_out kb=.. [fb=..] dtype [options]": fb is the reverse
# pattern's width, opt the update's slots (sgd: none, mom: momentum, adam:
# m and v); the update kernels carry the health flags unless nohealth.
CELL_MOSAIC = {
    # stablelm-3b's FFN, 8 x 1024 rows: both train cells (the fused cell
    # alone runs update_dw)
    "fwd 1x8192 2560>6912 kb=5 bf16 bm=512 bn=2":
        "ae87a1926a6c2f4e",
    "fwd 1x8192 6912>2560 kb=14 bf16 bm=256 bn=4":
        "938a68234f622aaf",
    "fwd 1x8192 2560>6912 kb=5 bf16 act=silu bm=512 bn=2 save":
        "42b80f8289c6414e",
    "dx 1x8192 6912>2560 kb=14 fb=6 bf16":
        "dad8cdf1a5357b3a",
    "dw 1x8192 6912>2560 kb=14 bf16":
        "a5bf5c19606d9593",
    "dx 1x8192 2560>6912 kb=5 fb=14 bf16":
        "3c3f45fd7b989ea7",
    "dw 1x8192 2560>6912 kb=5 bf16":
        "34679a693c030e02",
    "dx 1x8192 2560>6912 kb=5 fb=14 bf16 act=silu":
        "7d49c8694da672fe",
    "dw 1x8192 2560>6912 kb=5 bf16 act=silu":
        "85635399feaaff54",
    "update_dw 1x8192 6912>2560 kb=14 bf16 opt=adam":
        "106ef5628112ff6b",
    "update_dw 1x8192 2560>6912 kb=5 bf16 opt=adam":
        "d95a01784f26d9d6",
    "update_dw 1x8192 2560>6912 kb=5 bf16 act=silu opt=adam":
        "e33cf3e0d5cc4731",
    # DeepSeek-V2-Lite, 4 x 8192 rows: the dense layer and the shared
    # experts (E=1), the routed experts (E=8, counted)
    "fwd 8x32768 1408>2048 kb=3 bf16 bm=256 bn=8 counted":
        "76533283c6fdf7d6",
    "fwd 1x32768 2048>2816 kb=4 bf16 bm=512 bn=2":
        "0b32605c325209ff",
    "fwd 1x32768 2816>2048 kb=6 bf16 bm=512 bn=8":
        "f5c5dc63749258c6",
    "gated_fwd 8x32768 2048>1408 kb=4 bf16 bm=256 bn=1 save counted":
        "a5d2511bd0586e1f",
    "fwd 1x32768 2048>2816 kb=4 bf16 act=silu bm=512 bn=2 save":
        "3f2895834ecda3d2",
    "dx 1x32768 2816>2048 kb=6 fb=5 bf16":
        "2835b367798ceb92",
    "dw 1x32768 2816>2048 kb=6 bf16":
        "6095b0b4172ee2c1",
    "dx 1x32768 2048>2816 kb=4 fb=6 bf16":
        "2d03aeaa73263d87",
    "dw 1x32768 2048>2816 kb=4 bf16":
        "f982b55ca3e160f3",
    "dx 1x32768 2048>2816 kb=4 fb=6 bf16 act=silu":
        "c1e02305e3751b3a",
    "dw 1x32768 2048>2816 kb=4 bf16 act=silu":
        "7a29790f7de1d17e",
    "dx 8x32768 1408>2048 kb=3 fb=5 bf16 bm=256 counted":
        "e19d49554842889c",
    "dw 8x32768 1408>2048 kb=3 bf16 bm=256 counted":
        "e0018eb929b50afb",
    "gated_dx 8x32768 2048>1408 kb=4 fb=3 bf16 bm=256 counted":
        "ee6364ae23896925",
    "gated_dw 8x32768 2048>1408 kb=4 bf16 bm=256 counted":
        "12d4a20a9142d710",
    "update_dw 1x32768 2816>2048 kb=6 bf16 opt=adam":
        "3ad3c34ae5cc2b35",
    "update_dw 1x32768 2048>2816 kb=4 bf16 opt=adam":
        "32b9df997ae84452",
    "update_dw 1x32768 2048>2816 kb=4 bf16 act=silu opt=adam":
        "fed8d384a72aa56c",
    "update_dw 8x32768 1408>2048 kb=3 bf16 bm=256 opt=adam counted":
        "15b6c33f63529317",
    "update_gated_dw 8x32768 2048>1408 kb=4 bf16 bm=256 opt=adam counted":
        "c670197c0ef99fb7",
    # the population sweep: E=8 float32 members, 256-row steps and
    # 512-row evals, densities 0.125, 0.25 and 0.5
    "fwd 8x256 2560>6912 kb=2 f32 act=sigmoid bm=256 bn=2":
        "cb4c031e40dbf2ff",
    "fwd 8x256 6912>2560 kb=7 f32 act=sigmoid bm=128 bn=4":
        "b98662b648befdec",
    "dx 8x256 6912>2560 kb=7 fb=3 f32 act=sigmoid":
        "ab43f38cebc7b448",
    "update_dw 8x256 6912>2560 kb=7 f32 act=sigmoid bias opt=sgd":
        "f33203c5f2d4d00b",
    "update_dw 8x256 2560>6912 kb=2 f32 act=sigmoid bias opt=sgd":
        "70eb6b5f14759130",
    "fwd 8x512 2560>6912 kb=2 f32 act=sigmoid bm=256 bn=2":
        "cbced894f5cc0fa3",
    "fwd 8x512 6912>2560 kb=7 f32 act=sigmoid bm=128 bn=4":
        "050472cb1bb07dd8",
    "fwd 8x256 2560>6912 kb=5 f32 act=sigmoid bm=256 bn=2":
        "07f022f283342365",
    "fwd 8x256 6912>2560 kb=14 f32 act=sigmoid bm=128 bn=2":
        "a3ad730b8d4316c1",
    "dx 8x256 6912>2560 kb=14 fb=6 f32 act=sigmoid":
        "c8ce2a04cef6526c",
    "update_dw 8x256 6912>2560 kb=14 f32 act=sigmoid bias opt=sgd":
        "fb6dc7583b4dba8f",
    "update_dw 8x256 2560>6912 kb=5 f32 act=sigmoid bias opt=sgd":
        "f1e426b09464bcba",
    "fwd 8x512 2560>6912 kb=5 f32 act=sigmoid bm=256 bn=2":
        "6ae86d6a4539d8ea",
    "fwd 8x512 6912>2560 kb=14 f32 act=sigmoid bm=128 bn=2":
        "e1ccb8b8a5cd6026",
    "fwd 8x256 2560>6912 kb=10 f32 act=sigmoid bm=256 bn=2":
        "b56b4e229bd3bb4e",
    "fwd 8x256 6912>2560 kb=27 f32 act=sigmoid bm=128 bn=1":
        "87b4fee87f3518f1",
    "dx 8x256 6912>2560 kb=27 fb=10 f32 act=sigmoid":
        "850f478f84373595",
    "update_dw 8x256 6912>2560 kb=27 f32 act=sigmoid bias opt=sgd":
        "cc06555fd01edeeb",
    "update_dw 8x256 2560>6912 kb=10 f32 act=sigmoid bias opt=sgd":
        "f439320df656f5eb",
    "fwd 8x512 2560>6912 kb=10 f32 act=sigmoid bm=256 bn=2":
        "6f0b6900652e677e",
    "fwd 8x512 6912>2560 kb=27 f32 act=sigmoid bm=128 bn=1":
        "e3aeb3ed9974f61a",
    # the serve tick (32 slots) and prefill chunk (256 rows)
    "fwd 1x32 2560>6912 kb=5 bf16 act=silu bm=32 bn=2":
        "f2a4ed85086beaab",
    "fwd 1x32 2560>6912 kb=5 bf16 bm=32 bn=2":
        "cbc3575355b420a0",
    "fwd 1x32 6912>2560 kb=14 bf16 bm=32 bn=4":
        "65090c9eaf6e429f",
    "fwd 1x256 2560>6912 kb=5 bf16 act=silu bm=256 bn=2":
        "349580e9e7ec0d03",
    "fwd 1x256 2560>6912 kb=5 bf16 bm=256 bn=2":
        "746af83780a2bacc",
    "fwd 1x256 6912>2560 kb=14 bf16 bm=256 bn=4":
        "80059d576ae6a619",

    # beyond the cells: the quantized forwards (tests and
    # ServeConfig.quantize) and the update slot layouts no cell runs
    "fwd_int8 1x32 2560>6912 kb=5 bf16 act=silu bm=32 bn=2":
        "b77d0407dd21f73e",
    "fwd_int8 1x32 2560>6912 kb=5 bf16 bm=32 bn=2 xscale":
        "67ce79aa5562f4db",
    "gated_fwd_int8 8x256 2048>1408 kb=4 bf16 bm=256 bn=1":
        "4239cc9b58d67881",
    "gated_fwd_int8 8x256 2048>1408 kb=4 bf16 bm=256 bn=1 xscale":
        "daa983b0f028d285",
    "gated_fwd 8x256 2048>1408 kb=4 bf16 bm=256 bn=1":
        "b69a8e893f7243f2",
    "dw 4x1024 2560>6912 kb=5 bf16 act=sigmoid bias":
        "10b4f52d9261a099",
    "update_dw 4x1024 2560>6912 kb=5 bf16 act=sigmoid bias opt=adam":
        "0ff91aff0ac7eef0",
    "update_dw 1x1024 2560>6912 kb=5 f32 act=gelu bias opt=mom nohealth":
        "9a98b7e5f806d12c",
    "update_gated_dw 4x1024 2560>6912 kb=5 bf16 opt=mom nohealth":
        "8197f5440c6a0816",
}


def _cell_call(chip, case: str):
    """(fn, operands) of one CELL_MOSAIC configuration."""
    kernel, em, io, *rest = case.split()
    E, M = (int(v) for v in em.split("x"))
    n_in, n_out = (int(v) for v in io.split(">"))
    opt = {"act": "none", "fb": 0, "opt": "sgd"}
    for tok in rest:
        k, eq, v = tok.partition("=")
        opt[k] = (int(v) if v.isdigit() else v) if eq else True
    i32, f32 = jnp.int32, jnp.float32
    dt = jnp.bfloat16 if opt.get("bf16") else f32
    quant = kernel.endswith("int8")
    nob, nib, kb = n_out // BLOCK, n_in // BLOCK, opt["kb"]
    x, y = chip((E, M, n_in), dt), chip((E, M, n_out), dt)
    w = chip((E, nob, kb, BLOCK, BLOCK), jnp.int8 if quant else dt)
    idx = chip((nob, kb), i32)
    rev = {"rev_ob": chip((nib, opt["fb"]), i32),
           "rev_t": chip((nib, opt["fb"]), i32), "rev_cnt": chip((nib,), i32)}
    bias = chip((E, n_out), f32 if quant else dt)
    act, save, has_b = opt["act"], opt.get("save", False), "bias" in opt
    res = y if act != "none" else None
    scale = chip((E, nob, kb), f32)
    xs = chip((E,), f32) if "xscale" in opt else None
    mom, vel = opt["opt"] in ("mom", "adam"), opt["opt"] == "adam"
    slot = lambda on, a: chip(a.shape, f32) if on else None
    upd = {"hyp": chip((E, bsm.HYP_K), f32),
           "with_health": "nohealth" not in opt}
    args = {
        "fwd": dict(x=x, w=w, idx=idx, bias=bias, act=act, save_pre=save),
        "gated_fwd": dict(x=x, wg=w, wi=w, idx=idx, save_res=save),
        "fwd_int8": dict(x=x, wq=w, idx=idx, w_scale=scale, bias=bias,
                         act=act, x_scale=xs),
        "gated_fwd_int8": dict(x=x, wgq=w, wiq=w, idx=idx, wg_scale=scale,
                               wi_scale=scale, x_scale=xs),
        "dx": dict(dy=y, w=w, **rev, res=res, act=act),
        "gated_dx": dict(dh=y, wg=w, wi=w, **rev, g=y, u=y),
        "dw": dict(x=x, dy=y, idx=idx, res=res, act=act, with_bias=has_b),
        "gated_dw": dict(x=x, dh=y, idx=idx, g=y, u=y),
        "update_dw": dict(
            x=x, dy=y, idx=idx, res=res, w=w, b=bias if has_b else None,
            mom=slot(mom, w), mom_b=slot(mom and has_b, bias),
            vel=slot(vel, w), vel_b=slot(vel and has_b, bias), act=act,
            with_bias=has_b, **upd),
        "update_gated_dw": dict(
            x=x, dh=y, idx=idx, g=y, u=y, wg=w, wi=w, mg=slot(mom, w),
            mi=slot(mom, w), vg=slot(vel, w), vi=slot(vel, w), **upd),
    }[kernel]
    args.update({k: opt[k] for k in ("bm", "bn") if k in opt})
    if "counted" in opt:
        args["counts"] = chip((E,), i32)
    names = [k for k, v in args.items() if isinstance(v, jax.ShapeDtypeStruct)]
    static = {k: v for k, v in args.items() if k not in names}

    def call(*a):
        return getattr(bsm, kernel)(**dict(zip(names, a)), **static)
    return call, [args[k] for k in names]


@pytest.mark.parametrize("case", sorted(CELL_MOSAIC))
def test_cell_kernels_lower_as_before(chip, case):
    """Each kernel configuration the cells launch lowers, under its own
    name, to the Mosaic recorded for it: a change to the kernels' Python
    that leaves these alone leaves the device's programs alone."""
    fn, args = _cell_call(chip, case)
    with jax.default_matmul_precision("default"):
        txt = jax.jit(fn).lower(*args).as_text()
    name = ("expert_" if "counted" in case.split() else "") + (
        "junction_" + case.split()[0])
    assert re.findall(r'kernel_name = "([^"]+)"', txt) == [name]
    assert _mosaic_fingerprints(txt) == [CELL_MOSAIC[case]]
