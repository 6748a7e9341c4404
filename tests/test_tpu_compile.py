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


@pytest.mark.parametrize("hkv,rep,hd", [(32, 1, 80), (8, 8, 128)],
                         ids=["mha_hd80", "gqa_hd128"])
def test_flash_decode_compiles(chip, hkv, rep, hd):
    """stablelm-3b's head_dim 80 is not a lane multiple: the merged
    [P, ps, Hkv*hd] page layout keeps every DMA and load lane-aligned."""
    slots, ps, maxp = 8, 16, 36
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
