"""Pallas TPU kernels for pre-defined block-sparse matmul — ONE E-generic
edge-bundle engine, the paper's reconfigurable junction datapath.

The FPGA's core claim is that a single edge-processing datapath serves
every junction — reconfigured, not re-implemented, per layer.  Here that
is literal: there is exactly one kernel family, generic over a leading
expert dimension ``E``.  A single dense-model junction is the ``E=1``
case (``kernels/ops.junction_matmul`` squeezes it in and out); MoE expert
FFNs are ``E>1`` with per-expert weights ``[E, nob, kb, bs, bs]`` sharing
ONE block pattern that rides once in scalar prefetch — the paper's
"one junction shape, replicated units" reuse claim.

* **fwd** — grid ``(E, M/bm, nob/bn)``: one step computes ``bn`` output
  tiles for one expert.  The whole ``kb`` fan-in reduction runs *inside*
  the kernel body against an fp32 VMEM scratch accumulator (no output
  revisiting), and the bias + activation epilogue (the paper's FF-stage
  sigmoid fused into the edge pipeline) is applied before the single
  output write.  The activation row block ``[bm, nib*bs]`` stays
  VMEM-resident across the ``nob/bn`` bundle steps — the banked
  activation memory — while weight bundles stream through; the block
  index array rides in as a scalar-prefetch operand and drives in-kernel
  dynamic slices (the interleaver in SMEM).
* **dx** — grid ``(E, M/bm, nib)``: the reverse (fan-out) pattern
  reduction over ``fb`` runs in-body.  The reverse weight bundles are
  **DMA'd in-kernel**: the forward-layout weights stay in HBM
  (``memory_space=ANY``, viewed flat as ``[E, nob*kb, bs, bs]``) and the
  tiles at linear slot ``rev_ob[i,f]*kb + rev_t[i,f]`` are copied
  HBM→VMEM through double-buffered ``make_async_copy`` descriptors whose
  offsets come from the scalar-prefetched reverse pattern — no XLA
  ``w[rev_ob, rev_t]`` pre-gather, no w-sized HBM round-trip per
  backward step.  Reverse slots are consumed in **pairs**: when two
  consecutive slots are contiguous in the flat slot layout (``s1 ==
  s0 + 1`` — e.g. the last fan-in slot of one output block followed by
  the first of the next), ONE two-tile descriptor fetches both, halving
  descriptor overhead for high-fan-out patterns; non-contiguous pairs
  fall back to two single-tile descriptors, so scattered patterns pay
  exactly the pre-coalescing descriptor count.  The bundle is consumed
  un-transposed (the dot contracts both operands on their last dim).
  Padded reverse slots (``f >= rev_cnt[i]``, including whole input
  blocks with zero fan-out) carry in-bounds ``(0, 0)`` sentinels and
  their contribution is ``where``-masked — exact zeros even against
  non-finite upstream gradients.  The activation gradient is recomputed
  in the prologue from the saved residual (output y, or pre-activation s
  for silu/gelu), so the elementwise grad tensor ``dz`` never
  materializes in HBM.
* **dw** — grid ``(E, nob, M/bm)`` with the M reduction innermost into
  fp32 VMEM scratch, written once on the last step.  The ``kb`` gathered
  input blocks arrive through scalar-prefetch-driven BlockSpec
  index_maps (the interleaver as DMA descriptor), and the bias gradient
  accumulates in the same pass.
* **update** — the fused **BP+UP** stage (the paper's concurrent
  backprop + update pipeline): same grid and the same M-innermost
  VMEM-scratch gradient reduction as ``dw``, but instead of flushing the
  weight gradient to HBM the flush epilogue applies the optimizer update
  **in-kernel** on the last M step.  The optimizer is a STATIC switch
  keyed on which fp32 accumulator slots ride along (``_epilogue_step``):
  momentum-only runs SGD(+momentum), a second (m, v) slot pair runs Adam
  with per-step bias correction and decoupled weight decay — the
  hyperparameters come from the per-unit ``[E, HYP_K]`` hyp table in
  scalar prefetch (registry below).  Every parameter and accumulator
  operand comes in as a per-(e, ob) resident tile and leaves as an
  output declared with ``input_output_aliases``, so XLA rewrites the
  buffers in place — neither ``dw`` nor a second copy of ``w`` ever
  touches HBM.  The aliasing contract: every parameter operand maps to
  the output at the same relative position, the input/output BlockSpecs
  are identical, and each (e, ob) tile is read and written exactly once
  (the M loop is innermost), so no grid step can observe a
  partially-updated tile.  Accumulator slots are fp32 even for bf16
  params.

  With ``with_health=True`` the update kernels additionally emit a tiny
  **non-aliased** ``[E, 1, 128]`` int32 health output — the in-kernel
  divergence detector.  Because the in-place update means a non-finite
  ``dw`` silently destroys the parameter state (there is no HBM gradient
  to inspect downstream), the flush epilogue OR-reduces ``isfinite``
  over each post-momentum update tile (every weight operand, plus the
  bias update for biased layers) and accumulates a per-unit count of bad
  (e, ob) tiles: ``health[e] > 0`` ⇔ unit e wrote at least one
  non-finite parameter tile this step.  The slot is a single revisited
  ``(1, 1, 128)`` lane row per unit (zeroed at the first (ob, m) step,
  written only at flushes; a vector block because the TPU cannot store a
  scalar to VMEM) — one VMEM compare per tile, no gradient
  materialization, and the parameter outputs' aliasing contract is
  untouched.  ``ops.junction_train_update`` surfaces it as the cotangent
  of a dummy ``[E]`` health operand; ``train/steps.py`` aggregates it
  into ``metrics["nonfinite"]``.

Each product has one builder — ``junction_fwd``, ``junction_fwd_int8``,
``junction_dx``, ``junction_dw``, ``junction_update`` — over a tuple of
weight operands.  One operand is the plain junction; two, (Wg, Wi), are
the GShard/SwiGLU gate ``silu(x @ Wg) * (x @ Wi)`` fused into single
passes: both fan-in reductions accumulate side by side in VMEM scratch
in the forward, and the backward recomputes both branch gradients
(``dz_g = dh * u * silu'(g)``, ``dz_u = dh * silu(g)``) from the saved
``(g, u)`` residuals, ``dx`` double-buffering BOTH reverse weight
streams.  The live-count form (below) is the same builders given
``counts``.  ``fwd``/``gated_fwd``, ``fwd_int8``/``gated_fwd_int8``,
``dx``/``gated_dx``, ``dw``/``gated_dw`` and ``update_dw``/
``update_gated_dw`` are the per-form entry points over them; ``ops``
calls the update kernels through those two names, so a test can replace
either.  Where the one- and two-operand kernels have always traced the
same work in a different order, the builder keeps each order (the
``order:`` comments): another order lowers to another Mosaic program,
which ``tests/test_tpu_compile.py`` pins.

Hyp-column registry and accumulator-slot layout
-----------------------------------------------

``hyp`` is the per-unit ``[E, HYP_K]`` f32 hyperparameter table riding
scalar prefetch; the flush epilogue reads row ``e = program_id(0)``, so
every junction unit sharing the pattern trains under DIFFERENT
hyperparameters in the same launch (the population-search contract,
src/repro/search/; a single model is the ``E=1`` row).  The columns
(``HYP_COLS`` / ``COL_*`` constants — a cross-layer ABI shared with
``optim.FusedOptimizer.hyp`` rows, ``train/steps.py``'s lr/clip folds
and the population engine's sweep axes; append-only):

    col 0  lr    learning rate.  The guardian's backoff and any other
                 post-hoc lr scale multiply THIS column (no retrace).
    col 1  b1    SGD: momentum coefficient; Adam: first-moment decay.
    col 2  b2    Adam second-moment decay (ignored by the SGD branch).
    col 3  eps   Adam denominator epsilon.
    col 4  wd    Adam decoupled weight decay, applied as ``+ wd * w``.
    col 5  t     Adam 1-based step count for bias correction
                 (``c_i = 1 - b_i ** t``); the caller re-stamps it per
                 step (``FusedAdam.hyp`` / the sweep scheduler).
    col 6  gs    gradient pre-scale: the accumulated fp32 gradient is
                 multiplied by ``gs`` BEFORE the optimizer formula.
                 Global-norm grad clipping folds in here EXACTLY —
                 folding a clip scale into lr instead would warp the
                 momentum/Adam accumulator state.  1 on the unscaled
                 path; 0 (with the whole row zeroed) freezes a
                 pruned/quarantined unit in place.

A legacy ``[lr, momentum]`` pair — ``(2,)`` or ``[E, 2]`` — normalizes
to ``[lr, momentum, 0, 0, 0, 0, 1]`` (``normalize_hyp``), bitwise
identical SGD numerics.

Accumulator slots are fp32 tensors shaped like the weight (bias)
operand they accompany, aliased in place exactly like the weights;
WHICH slots ride along is the static optimizer switch — no hyp column
selects the optimizer, the operand list does:

    SGD            w [, b]                          (no slots)
    SGD+momentum   w, mom [, b, mom_b]              slot 0 = velocity
    Adam           w, mom, vel [, b, mom_b, vel_b]  slot 0 = first
                   moment m, slot 1 = second moment v

Operand order (and the mirrored output order) is always
``w, slots..., b, bias slots...``; the gated kernel interleaves
``wg, wi, mg, mi, vg, vi``.  To add an optimizer: append its columns
to ``HYP_COLS``, add its slot(s) to this layout (and to
``core/sparse_linear.FUSED_SLOT_NAMES``), and give ``_epilogue_step``
a new statically-selected branch.  The Adam branch's guards (zero
bias-correction denominators and a zero update denominator resolve to
an exact-zero update) exist so an all-zero hyp row freezes a unit under
EITHER optimizer; with real hyperparameters the guards are inert and
the math matches ``optim.adam``'s two-pass update to fp32 round-off.

Quantized inference variants (PR 8)
-----------------------------------

``junction_fwd_int8`` and ``fwd_fxp`` are forward-only counterparts of
``junction_fwd`` for post-training-quantized weights
(``core/quantize.py`` builds the operands at checkpoint-load time; no
custom_vjp — ``junction_train_update`` refuses integer codes):

* **fwd_int8** — weights arrive as int8 codes with symmetric per-block
  scales ``w_scale [E, nob, kb]`` riding scalar prefetch EXACTLY like
  the pattern leaves (per-unit "unit" granularity is the same layout,
  broadcast at quantize time — one kernel contract).  Per fan-in slot
  the activation tile is quantized in-body (dynamic per-row absmax/127,
  or a calibrated static per-unit ``x_scale [E]`` prefetch leaf), the
  int8×int8 dot accumulates exactly in int32 on the MXU, and the
  dequant ``p * (sx * w_scale[e, ob, k])`` lands in the SAME fp32 VMEM
  scratch reduction slot the fp forward uses — bias + activation
  epilogue unchanged.  The multiplication grouping and per-k
  accumulation order are mirrored op-for-op by the jnp sim
  (``core/quantize.apply_quant_jnp``) so engine parity is exact.
* **fwd_fxp** — the paper's full fixed-point pipeline: activations are
  encoded in-body to the bit-triplet grid (``round(x * 2^bf)``,
  saturated), products accumulate in an **int32** VMEM scratch, and the
  epilogue is round-half-up shift by bf → saturate → bias ``q_add`` →
  VMEM-resident LUT activation (``jnp.take`` over the full 2^bw-entry
  table, indexed by two's-complement code — the BRAM sigmoid table).
  ``qfmt = [bf, bn_bits]`` rides as a traced i32 scalar-prefetch leaf;
  the saturate bound is static from the LUT length.  The runtime
  ``act`` is ignored — the LUT (baked at quantize time) IS the
  activation.
* **two int8 operands** (the gate) — both branches dotted in int8 with
  per-branch scale prefetch leaves, shared in-body activation codes,
  two fp32 scratch accumulators, ``silu(g) * u`` epilogue unchanged.

Tile tuning — one table for every configuration
-----------------------------------------------

``TUNE_TABLE`` maps a 6-key

    (E, M, nob, kb, bs, n_weight_operands) -> (bm, bn)

where ``E`` is the expert count (1 for single junctions), ``M`` the
*unpadded* row count the public wrapper sees, ``nob``/``kb``/``bs`` the
output-block/fan-in/block-size shape, and ``n_weight_operands`` the
number of weight tensors streamed per step (2 for the gated kernel —
its entries are tuned for double the weight-bundle residency).
``n_weight_operands`` counts *forward* weight streams only: the fused
update kernels keep their extra parameter tiles (w + fp32 momentum, and
their aliased outputs) resident per (e, ob) rather than streaming them
per step, and they reuse the forward's tune entry for the row tile via
the ``bwd_bm`` clamp — deliberately the SAME default ``bm`` as the
plain ``dw`` kernels so the fp32 gradient accumulation order matches
the two-pass reference (updated params agree to fp32 round-off; only
XLA's fma fusion of the epilogue differs between the two programs).

Table entries come from chip runs recorded in ``PERF.md``.
Misses fall back to a VMEM-budget heuristic (``choose_tiles``).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Hyp-column registry: the [E, HYP_K] table's cross-layer ABI.  Append
# only — see the module docstring's registry section before changing.
HYP_COLS = ("lr", "b1", "b2", "eps", "wd", "t", "gs")
HYP_K = len(HYP_COLS)
COL_LR, COL_B1, COL_B2, COL_EPS, COL_WD, COL_T, COL_GS = range(HYP_K)

# Activations whose gradient needs the pre-activation s (saved as a second
# forward output); the rest reconstruct the gradient from y itself.
ACT_NEEDS_PRE = ("silu", "gelu")
ACTIVATIONS = ("none", "relu", "sigmoid", "silu", "gelu")

_GELU_C = 0.7978845608028654  # sqrt(2/pi)
_GELU_A = 0.044715


def act_fwd(s, act: str):
    """Epilogue activation on the fp32 accumulator.  gelu is the tanh
    approximation — the same formula jax.nn.gelu(approximate=True) uses,
    so engine="pallas" and engine="jnp" agree bit-for-bit in structure."""
    if act == "none":
        return s
    if act == "relu":
        return jnp.maximum(s, 0.0)
    if act == "sigmoid":
        return jax.nn.sigmoid(s)
    if act == "silu":
        return s * jax.nn.sigmoid(s)
    if act == "gelu":
        u = _GELU_C * (s + _GELU_A * s * s * s)
        return 0.5 * s * (1.0 + jnp.tanh(u))
    raise ValueError(f"unknown activation {act!r}")


def act_bwd(res, act: str):
    """d act/d s from the residual: y for relu/sigmoid, s for silu/gelu."""
    if act == "none":
        return None  # caller skips the multiply entirely
    if act == "relu":
        return (res > 0.0).astype(jnp.float32)
    if act == "sigmoid":
        return res * (1.0 - res)
    if act == "silu":
        sg = jax.nn.sigmoid(res)
        return sg * (1.0 + res * (1.0 - sg))
    if act == "gelu":
        s = res
        u = _GELU_C * (s + _GELU_A * s * s * s)
        t = jnp.tanh(u)
        du = _GELU_C * (1.0 + 3.0 * _GELU_A * s * s)
        return 0.5 * (1.0 + t) + 0.5 * s * (1.0 - t * t) * du
    raise ValueError(f"unknown activation {act!r}")


# ------------------------------------------------------------- tile tuning
VMEM_BUDGET = 8 * 1024 * 1024   # conservative per-kernel working-set bound
MAX_BN = 8
WEIGHT_BUNDLE_BUDGET = 2 * 1024 * 1024  # per-step streamed-weight bound


# Measured entries: (E, M, nob, kb, bs, n_weight_operands) -> (bm, bn).
TUNE_TABLE: dict[tuple[int, int, int, int, int, int], tuple[int, int]] = {
    # paper MNIST junction (12544-sample epoch, 1024->512 @ kb=2)
    (1, 12544, 4, 2, 128, 1): (512, 4),
    # transformer FFN up-projection bench shape (1024->4096 @ kb=2)
    (1, 4096, 32, 2, 128, 1): (256, 8),
}


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _choose_bm(M: int, row_blocks: int, bs: int, itemsize: int) -> int:
    """Largest row-tile (multiple of 16 sublanes) whose resident row block
    ``[bm, row_blocks*bs]`` fits half the VMEM budget."""
    row_bytes = max(1, row_blocks * bs * itemsize)
    bm = 512
    while bm > 16 and bm * row_bytes > VMEM_BUDGET // 2:
        bm //= 2
    return max(16, min(bm, _round_up(M, 16)))


def _choose_bn(nob: int, kb: int, bs: int, itemsize: int,
               budget: int) -> int:
    """Largest power-of-two divisor of nob whose weight bundle fits the
    per-step VMEM budget."""
    bn = 1
    while (bn < MAX_BN and nob % (2 * bn) == 0
           and 2 * bn * kb * bs * bs * itemsize <= budget):
        bn *= 2
    return bn


def choose_tiles(M: int, nob: int, kb: int, bs: int, nib: int,
                 itemsize: int = 4, *, E: int = 1,
                 n_weight_operands: int = 1) -> tuple[int, int]:
    """(bm, bn) for the fused forward of ANY junction configuration:
    TUNE_TABLE first, then the
    VMEM heuristic — bm bounded by the resident x row block (one expert's
    row block is resident per grid step, so the bound is E-independent),
    bn the largest power-of-two divisor of nob whose weight bundle fits
    the per-step budget split across the streamed weight tensors."""
    hit = TUNE_TABLE.get((E, M, nob, kb, bs, n_weight_operands))
    if hit is not None:
        bm, bn = hit
        return max(16, min(bm, _round_up(M, 16))), bn
    bm = _choose_bm(M, nib, bs, itemsize)
    budget = WEIGHT_BUNDLE_BUDGET // max(1, n_weight_operands)
    return bm, _choose_bn(nob, kb, bs, itemsize, budget)


def bwd_bm(M: int, row_blocks: int, bs: int, itemsize: int) -> int:
    """Row tile for the backward kernels: the forward's VMEM-residency
    bound, gcd-clamped to divide the (pre-padded by the forward's bm, a
    multiple of 16) row count M exactly."""
    return math.gcd(_choose_bm(M, row_blocks, bs, itemsize), M)


def fwd_grid(M: int, nob: int, kb: int, bs: int, nib: int,
             itemsize: int = 4, E: int = 1) -> tuple[int, int]:
    """Per-expert grid of the fused forward for padded row count M — the
    acceptance bound: exactly (M/bm) * (nob/bn) steps per expert, kb
    fully in-kernel."""
    bm, bn = choose_tiles(M, nob, kb, bs, nib, itemsize, E=E)
    return (_round_up(M, bm) // bm, nob // bn)


# ------------------------------------------------------ live row counts
# An expert layer that dispatches by index (models/moe.py) hands the
# E-batched kernels a buffer [E, M, n] whose unit e holds ``counts[e]``
# live rows at its top and nothing below.  With ``counts`` (int32 [E],
# the last scalar-prefetch operand) a row tile at or past its unit's
# count is neither fetched nor computed: the body is skipped under
# ``pl.when`` and every index map sends the step to the unit's last
# live block (so no DMA moves in or out).  Rows past a count are left
# unwritten in the outputs; the caller never reads them.  The update
# kernels skip the tile's gradient reduction only: the flush epilogue
# runs for every (unit, output block), so a unit with no rows still
# takes its optimizer step from its moments.  Counted calls carry the
# name prefix ``expert_`` (``expert_junction_gated_fwd`` ...), so a
# device trace tells the routed experts from the dense junctions.
EXPERT_PREFIX = "expert_"


def _kernel_name(name: str, counts) -> str:
    return name if counts is None else EXPERT_PREFIX + name


def _live(cnt_ref, e, m, bm):
    """Whether row tile m of unit e holds a live row."""
    return m * bm < cnt_ref[e]


def _rows_outer(index_map, bm: int, n_inner: int):
    """A counted grid (E, M/bm, n_inner)'s index map: steps past unit
    e's live row tiles go to its last live step's block."""
    def counted(e, m, j, *refs):
        *pref, cnt = refs
        live = _live(cnt, e, m, bm)
        last = jnp.maximum((cnt[e] + bm - 1) // bm - 1, 0)
        return index_map(e, jnp.where(live, m, last),
                         jnp.where(live, j, n_inner - 1), *pref)
    return counted


def _rows_inner(index_map, bm: int):
    """A counted grid (E, nob, M/bm)'s index map (M innermost): row
    tiles past unit e's count re-point at its last live tile."""
    def counted(e, o, m, *refs):
        *pref, cnt = refs
        last = jnp.maximum((cnt[e] + bm - 1) // bm - 1, 0)
        return index_map(e, o, jnp.minimum(m, last), *pref)
    return counted


def _counted_specs(specs, counts, wrap):
    if counts is None:
        return specs
    return [dataclasses.replace(s, index_map=wrap(s.index_map))
            if s.index_map is not None else s for s in specs]


def _run_live(compute, cnt_ref, bm: int, row_axis: int):
    """Run a kernel body's work; with a counts ref, only for live row
    tiles.  The program ids are read here, outside the conditional
    (interpret mode lowers none inside one)."""
    if cnt_ref is None:
        compute()
    else:
        live = _live(cnt_ref, pl.program_id(0), pl.program_id(row_axis), bm)
        pl.when(live)(compute)


# ------------------------------------------------------------------ forward
def _gate(ws) -> str:
    """The kernel-name infix of a junction with weight operands ``ws``."""
    return "gated_" if len(ws) == 2 else ""


def _fwd_tiles(M, nob, kb, bs, nib, itemsize, E, n_w, bm, bn):
    """(bm, bn) of a forward call: the caller's, else ``choose_tiles``."""
    cbm, cbn = choose_tiles(M, nob, kb, bs, nib, itemsize, E=E,
                            n_weight_operands=n_w)
    bm = cbm if bm is None else bm
    bn = cbn if bn is None else bn
    if nob % bn:
        bn = 1
    assert M % bm == 0, f"M={M} must be a multiple of bm={bm} (pad in ops.py)"
    return bm, bn


def _fwd_in_specs(bm, bn, nib, kb, bs, n_w, with_bias):
    # the full activation row block, resident across bundle steps; then
    # each weight operand's bundle, and the bias's tiles
    specs = [pl.BlockSpec((1, bm, nib * bs), lambda e, m, o, *_: (e, m, 0))]
    specs += [pl.BlockSpec((1, bn, kb, bs, bs),
                           lambda e, m, o, *_: (e, o, 0, 0, 0))] * n_w
    if with_bias:
        specs.append(pl.BlockSpec((1, 1, bn * bs),
                                  lambda e, m, o, *_: (e, 0, o)))
    return specs


def _fwd_out(acc_refs, b_ref, act, save_refs, o_ref):
    """The forward's single output write from its fp32 reductions: bias
    and activation for one weight operand, the gate ``silu(g) * u`` for
    two.  The backward's residuals (the pre-activation, or g and u) are
    stored first."""
    zs = [r[...] for r in acc_refs]
    if b_ref is not None:
        zs[0] = zs[0] + b_ref[0].astype(jnp.float32)
    for r, z in zip(save_refs, zs):
        r[0] = z.astype(r.dtype)
    out = act_fwd(zs[0], act if len(zs) == 1 else "silu")
    if len(zs) == 2:
        out = out * zs[1]
    o_ref[0] = out.astype(o_ref.dtype)


def junction_fwd(x, ws, idx, bias, *, act: str = "none",
                 bm: int | None = None, bn: int | None = None,
                 save: bool = False, interpret: bool = False, counts=None):
    """The forward of one junction product per unit: x [E, M, nib*bs],
    weight operands ``ws`` (each [E, nob, kb, bs, bs]), shared idx
    [nob, kb] -> [E, M, nob*bs].  One operand gives act(x_e @ W_e + b_e)
    with bias [E, nob*bs]; two, (Wg, Wi), give the SiLU gate
    silu(x_e @ Wg_e) * (x_e @ Wi_e), no bias (``bias`` None, ``act``
    unused), both fan-in reductions side by side in VMEM scratch.

    Grid (E, M/bm, nob/bn): the expert dimension is the outermost grid
    axis; the pattern rides once in scalar prefetch and is reused by every
    unit.  One step computes bn output tiles — the kb fan-in slots reduce
    in-body into fp32 VMEM scratch, epilogue fused, single output write.
    ``counts`` [E] int32: live rows per unit (see "live row counts").
    Returns ``(out, saved)``: with ``save``, saved is the backward's
    residuals, (pre-activation,) or (g, u); else None."""
    n = len(ws)
    E, M, _ = x.shape
    _, nob, kb, bs, _ = ws[0].shape
    nib = x.shape[2] // bs
    bm, bn = _fwd_tiles(M, nob, kb, bs, nib, x.dtype.itemsize, E, n, bm, bn)
    n_out = 1 + (n if save else 0)
    with_bias = bias is not None

    def kernel(idx_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        x_ref, *refs = refs
        w_refs, refs = refs[:n], refs[n:]
        b_ref = refs.pop(0) if with_bias else None
        o_ref, save_refs, acc_refs = refs[0], refs[1:n_out], refs[n_out:]
        ob0 = pl.program_id(2) * bn

        def _compute():
            for j in range(bn):
                accs = [jnp.zeros((bm, bs), jnp.float32) for _ in ws]
                for k in range(kb):
                    ib = idx_ref[ob0 + j, k]
                    xk = x_ref[0, :, pl.ds(ib * bs, bs)]
                    accs = [a + jnp.dot(xk, w_ref[0, j, k],
                                        preferred_element_type=jnp.float32)
                            for a, w_ref in zip(accs, w_refs)]
                for acc_ref, a in zip(acc_refs, accs):
                    acc_ref[:, j * bs:(j + 1) * bs] = a
            _fwd_out(acc_refs, b_ref, act, save_refs, o_ref)

        _run_live(_compute, cnt_ref, bm, 1)

    operands = [x, *ws] + ([bias.reshape(E, 1, -1)] if with_bias else [])
    out_specs = [pl.BlockSpec((1, bm, bn * bs),
                              lambda e, m, o, *_: (e, m, o))] * n_out
    prefetch = (idx,) if counts is None else (idx, counts)
    rows = lambda im: _rows_outer(im, bm, nob // bn)
    outs = pl.pallas_call(
        kernel,
        name=_kernel_name(f"junction_{_gate(ws)}fwd", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, M // bm, nob // bn),
            in_specs=_counted_specs(
                _fwd_in_specs(bm, bn, nib, kb, bs, n, with_bias), counts,
                rows),
            out_specs=_counted_specs(out_specs, counts, rows),
            scratch_shapes=[pltpu.VMEM((bm, bn * bs), jnp.float32)
                            for _ in ws],
        ),
        out_shape=[jax.ShapeDtypeStruct((E, M, nob * bs), x.dtype)] * n_out,
        interpret=interpret,
    )(*prefetch, *operands)
    return outs[0], (tuple(outs[1:]) if save else None)


def fwd(x, w, idx, bias, *, act: str = "none", bm: int | None = None,
        bn: int | None = None, save_pre: bool = False,
        interpret: bool = False, counts=None):
    """x [E, M, nib*bs], w [E, nob, kb, bs, bs], shared idx [nob, kb],
    bias [E, nob*bs] -> (act(x_e @ W_e + b_e) [E, M, nob*bs], the
    pre-activation if save_pre else None): ``junction_fwd`` of one
    weight operand."""
    y, saved = junction_fwd(x, (w,), idx, bias, act=act, bm=bm, bn=bn,
                            save=save_pre, interpret=interpret, counts=counts)
    return y, (saved[0] if save_pre else None)


def gated_fwd(x, wg, wi, idx, *, bm: int | None = None,
              bn: int | None = None, save_res: bool = False,
              interpret: bool = False, counts=None):
    """The fused SiLU-gate FFN entry silu(x_e @ Wg_e) * (x_e @ Wi_e):
    ``junction_fwd`` of two weight operands.  Returns (h, g_pre, u) —
    the gate's pre-activation g and the linear branch u (the backward's
    residuals) only when save_res, else None."""
    h, saved = junction_fwd(x, (wg, wi), idx, None, bm=bm, bn=bn,
                            save=save_res, interpret=interpret, counts=counts)
    return (h, *saved) if save_res else (h, None, None)


# ------------------------------------------------------ quantized forward
def _slot_x_scale(xk, xs):
    """In-kernel activation quantization scale for one gathered fan-in
    slot: dynamic per-row absmax/127 (never looks across the row tile,
    so it is bitwise engine-independent), or the calibrated static
    per-unit scale."""
    if xs is None:
        ax = jnp.max(jnp.abs(xk), axis=-1, keepdims=True)
        return jnp.where(ax == 0.0, 1.0, ax / 127.0)
    return xs


def junction_fwd_int8(x, ws, idx, scales, bias, *, act: str = "none",
                      x_scale=None, bm: int | None = None,
                      bn: int | None = None, interpret: bool = False):
    """int8 forward: x [E, M, nib*bs] fp, int8 weight operands ``ws``
    (each [E, nob, kb, bs, bs]) with their per-block scales ``scales``
    (each [E, nob, kb] f32, on scalar prefetch), shared idx [nob, kb] ->
    [E, M, nob*bs]: act(dequant(xq @ wq) + b) with bias [E, nob*bs] for
    one operand, silu(dequant(xq @ wgq)) * dequant(xq @ wiq) for two
    (``bias`` None), the activation codes shared.  Optional x_scale [E]
    f32 switches activation quantization from dynamic per-row to
    calibrated static per-unit."""
    n = len(ws)
    E, M, _ = x.shape
    _, nob, kb, bs, _ = ws[0].shape
    nib = x.shape[2] // bs
    bm, bn = _fwd_tiles(M, nob, kb, bs, nib, x.dtype.itemsize, E, n, bm, bn)
    has_xs = x_scale is not None
    with_bias = bias is not None

    def kernel(idx_ref, *refs):
        sc_refs, refs = refs[:n], list(refs[n:])
        xs_ref = refs.pop(0) if has_xs else None
        x_ref, *refs = refs
        w_refs, refs = refs[:n], refs[n:]
        b_ref = refs.pop(0) if with_bias else None
        o_ref, *acc_refs = refs
        e = pl.program_id(0)
        ob0 = pl.program_id(2) * bn
        for j in range(bn):
            accs = [jnp.zeros((bm, bs), jnp.float32) for _ in ws]
            for k in range(kb):
                ib = idx_ref[ob0 + j, k]
                xk = x_ref[0, :, pl.ds(ib * bs, bs)].astype(jnp.float32)
                sx = _slot_x_scale(xk, xs_ref[e] if has_xs else None)
                xq = jnp.clip(jnp.round(xk / sx), -127, 127
                              ).astype(jnp.int8)
                ps = [jnp.dot(xq, w_ref[0, j, k],
                              preferred_element_type=jnp.int32)
                      for w_ref in w_refs]
                # dequant into the fp32 reduction slot; grouping matches
                # the jnp sim exactly (see core/quantize._int8_apply)
                accs = [a + p.astype(jnp.float32) * (
                    sx * sc_ref[e, ob0 + j, k])
                    for a, p, sc_ref in zip(accs, ps, sc_refs)]
            for acc_ref, a in zip(acc_refs, accs):
                acc_ref[:, j * bs:(j + 1) * bs] = a
        _fwd_out(acc_refs, b_ref, act, (), o_ref)

    prefetch = (idx, *scales) + ((x_scale,) if has_xs else ())
    operands = [x, *ws] + ([bias.reshape(E, 1, -1)] if with_bias else [])
    out = pl.pallas_call(
        kernel,
        name=f"junction_{_gate(ws)}fwd_int8",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, M // bm, nob // bn),
            in_specs=_fwd_in_specs(bm, bn, nib, kb, bs, n, with_bias),
            out_specs=[pl.BlockSpec((1, bm, bn * bs),
                                    lambda e, m, o, *_: (e, m, o))],
            scratch_shapes=[pltpu.VMEM((bm, bn * bs), jnp.float32)
                            for _ in ws],
        ),
        out_shape=[jax.ShapeDtypeStruct((E, M, nob * bs), x.dtype)],
        interpret=interpret,
    )(*prefetch, *operands)
    return out[0]


def fwd_int8(x, wq, idx, w_scale, bias, *, act: str = "none",
             x_scale=None, bm: int | None = None, bn: int | None = None,
             interpret: bool = False):
    """int8 forward of one weight operand: x [E, M, nib*bs] fp, wq
    [E, nob, kb, bs, bs] int8, shared idx [nob, kb], w_scale [E, nob, kb]
    f32, bias [E, nob*bs] -> act(dequant(xq @ wq) + b) [E, M, nob*bs]
    (``junction_fwd_int8``)."""
    return junction_fwd_int8(x, (wq,), idx, (w_scale,), bias, act=act,
                             x_scale=x_scale, bm=bm, bn=bn,
                             interpret=interpret)


def gated_fwd_int8(x, wgq, wiq, idx, wg_scale, wi_scale, *, x_scale=None,
                   bm: int | None = None, bn: int | None = None,
                   interpret: bool = False):
    """int8 twin of gated_fwd: silu(dequant(xq @ wgq)) * dequant(xq @
    wiq), per-branch scales [E, nob, kb] (``junction_fwd_int8`` of two
    weight operands)."""
    return junction_fwd_int8(x, (wgq, wiq), idx, (wg_scale, wi_scale), None,
                             x_scale=x_scale, bm=bm, bn=bn,
                             interpret=interpret)


def fwd_fxp(x, wq, idx, qfmt, lut, bias, *, bm: int | None = None,
            bn: int | None = None, interpret: bool = False):
    """Full fixed-point forward: wq [E, nob, kb, bs, bs] int32 triplet
    codes, qfmt [2] i32 = [bf, bn_bits] on scalar prefetch, lut [2^bw]
    f32 VMEM-resident activation table, bias [E, nob*bs] fp (snapped to
    the grid at quantize time).  Activations encode in-body; the int32
    accumulation + round-half-up shift + saturate + bias q_add + LUT
    epilogue is bit-exact fixed-point arithmetic — no runtime act."""
    E, M, _ = x.shape
    _, nob, kb, bs, _ = wq.shape
    nib = x.shape[2] // bs
    T = lut.shape[0]
    lim = T // 2   # static saturate bound: 2^(bn_bits + bf)
    cbm, cbn = choose_tiles(M, nob, kb, bs, nib, x.dtype.itemsize, E=E)
    bm = cbm if bm is None else bm
    bn = cbn if bn is None else bn
    if nob % bn:
        bn = 1
    assert M % bm == 0, f"M={M} must be a multiple of bm={bm} (pad in ops.py)"

    def fwd_fxp_kernel(idx_ref, qf_ref, x_ref, w_ref, b_ref, lut_ref,
                       o_ref, acc_ref):
        bf = qf_ref[0]
        scale = jnp.exp2(bf.astype(jnp.float32))
        ob0 = pl.program_id(2) * bn
        for j in range(bn):
            acc = jnp.zeros((bm, bs), jnp.int32)
            for k in range(kb):
                ib = idx_ref[ob0 + j, k]
                xk = x_ref[0, :, pl.ds(ib * bs, bs)].astype(jnp.float32)
                xq = jnp.clip(jnp.round(xk * scale), -lim, lim - 1
                              ).astype(jnp.int32)
                acc = acc + jnp.dot(xq, w_ref[0, j, k],
                                    preferred_element_type=jnp.int32)
            acc_ref[:, j * bs:(j + 1) * bs] = acc
        half = jnp.left_shift(jnp.int32(1), bf - 1)
        s = jnp.right_shift(acc_ref[...] + half, bf)   # round half up
        s = jnp.clip(s, -lim, lim - 1)                 # saturating adder
        bcode = jnp.clip(jnp.round(b_ref[0].astype(jnp.float32) * scale),
                         -lim, lim - 1).astype(jnp.int32)
        s = jnp.clip(s + bcode, -lim, lim - 1)         # q_add
        o_ref[0] = jnp.take(lut_ref[...], jnp.bitwise_and(s, T - 1),
                            axis=0).astype(o_ref.dtype)

    out = pl.pallas_call(
        fwd_fxp_kernel,
        name="junction_fwd_fxp",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E, M // bm, nob // bn),
            in_specs=[
                pl.BlockSpec((1, bm, nib * bs), lambda e, m, o, *_: (e, m, 0)),
                pl.BlockSpec((1, bn, kb, bs, bs),
                             lambda e, m, o, *_: (e, o, 0, 0, 0)),
                pl.BlockSpec((1, 1, bn * bs), lambda e, m, o, *_: (e, 0, o)),
                # the whole activation table, VMEM-resident every step
                pl.BlockSpec((T,), lambda e, m, o, *_: (0,)),
            ],
            out_specs=[pl.BlockSpec((1, bm, bn * bs),
                                    lambda e, m, o, *_: (e, m, o))],
            scratch_shapes=[pltpu.VMEM((bm, bn * bs), jnp.int32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((E, M, nob * bs), x.dtype)],
        interpret=interpret,
    )(idx, qfmt, x, wq, bias.reshape(E, 1, -1), lut)
    return out[0]


# ------------------------------------------------------------------ dx
def _rev_dot(dz, wb):
    """dz [bm, bs_out] x forward-layout bundle wb [bs_in, bs_out] ->
    [bm, bs_in]: contract both on their LAST dim (dz @ wb.T without a
    transpose copy of the DMA'd tile)."""
    return jax.lax.dot_general(dz, wb, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _pair_copies(w_hbm, wbuf, sems, e, s0, s1, buf):
    """The (descriptor, condition) list fetching the reverse slot pair
    (s0, s1) of the flat [E, nob*kb, bs, bs] weight view into buffer line
    ``wbuf[buf]``: ONE two-tile descriptor when the slots are contiguous
    in the flat layout, else one single-tile descriptor per slot.  s1 is
    None for the trailing half-pair of an odd fan-out.  Called with
    identical arguments from the start and the wait sides so the
    conditional descriptors always match their semaphores."""
    if s1 is None:
        return [(pltpu.make_async_copy(w_hbm.at[e, pl.ds(s0, 1)],
                                       wbuf.at[buf, pl.ds(0, 1)],
                                       sems.at[buf, 0]), None)]
    contig = s1 == s0 + 1
    apart = jnp.logical_not(contig)
    return [
        (pltpu.make_async_copy(w_hbm.at[e, pl.ds(s0, 2)], wbuf.at[buf],
                               sems.at[buf, 0]), contig),
        (pltpu.make_async_copy(w_hbm.at[e, pl.ds(s0, 1)],
                               wbuf.at[buf, pl.ds(0, 1)],
                               sems.at[buf, 0]), apart),
        (pltpu.make_async_copy(w_hbm.at[e, pl.ds(s1, 1)],
                               wbuf.at[buf, pl.ds(1, 1)],
                               sems.at[buf, 1]), apart),
    ]


def _run_copies(copies, method: str):
    for copy, cond in copies:
        fn = getattr(copy, method)
        if cond is None:
            fn()
        else:
            pl.when(cond)(fn)


def _gate_dz(dh, g, u, dtype):
    """Both branch gradients of the gate h = silu(g) * u from its fp32
    residual tiles: dz_g = dh * u * silu'(g), dz_u = dh * silu(g)."""
    dzg = (dh * u * act_bwd(g, "silu")).astype(dtype)
    dzu = (dh * act_fwd(g, "silu")).astype(dtype)
    return dzg, dzu


def junction_dx(dy, ws, rev_ob, rev_t, rev_cnt, res, *, act: str = "none",
                bm: int | None = None, interpret: bool = False, counts=None):
    """dy [E, M, nob*bs] -> dx [E, M, nib*bs] via the shared reverse
    (fan-out) pattern against the forward-layout weight operands ``ws``
    (each [E, nob, kb, bs, bs]).  ``res`` holds the forward's residuals:
    none (act "none"), the pre-activation or output ``act`` needs, or the
    gate's (g, u) with two operands, whose two branch gradients reduce
    against their own reverse bundles in the same fb loop.

    The reverse weight bundles are DMA'd in-kernel: w stays in HBM
    (memory_space=ANY, viewed flat over the (nob, kb) slot dims) and the
    tiles at linear slot rev_ob[i,f]*kb + rev_t[i,f] are double-buffered
    HBM→VMEM with make_async_copy, offsets from the scalar-prefetched
    reverse pattern — the XLA w[rev_ob, rev_t] pre-gather (a w-sized
    round-trip per backward call) is gone.  Slots are fetched in PAIRS:
    contiguous runs in the flat slot layout coalesce into one two-tile
    descriptor (halved descriptor overhead for high-fan-out patterns),
    scattered pairs fall back to two single-tile descriptors.  Padded
    slots (f >= rev_cnt[i], (0,0) sentinels) prefetch an in-bounds bundle
    whose contribution is where-masked, so zero-fan-out input blocks
    yield exact-zero dx rows even for non-finite dy.  The activation
    gradient is recomputed per dy block from the residuals.  ``counts``:
    live rows per unit, as in ``junction_fwd``."""
    n = len(ws)
    E, M, _ = dy.shape
    _, nob, kb, bs, _ = ws[0].shape
    nib, fb = rev_ob.shape
    if bm is None:
        bm = bwd_bm(M, nob * (1 + len(res)), bs, dy.dtype.itemsize)
    assert M % bm == 0
    npair = (fb + 1) // 2
    w_flats = [w.reshape(E, nob * kb, bs, bs) for w in ws]

    def kernel(rev_ob_ref, rev_t_ref, rev_cnt_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        dy_ref, *refs = refs
        res_refs, refs = refs[:len(res)], refs[len(res):]
        w_hbms, o_ref, bufs, sems = (refs[:n], refs[n], refs[n + 1:2 * n + 1],
                                     refs[2 * n + 1])
        e = pl.program_id(0)
        i = pl.program_id(2)
        cnt = rev_cnt_ref[i]

        def slot(f):
            return rev_ob_ref[i, f] * kb + rev_t_ref[i, f]

        def copies(buf, p):
            f0 = 2 * p
            second = lambda: slot(f0 + 1) if f0 + 1 < fb else None
            if n == 1:   # order: the pair's second slot is read first
                s1 = second()
                return _pair_copies(w_hbms[0], bufs[0], sems, e, slot(f0), s1,
                                    buf)
            s0, s1 = slot(f0), second()
            return [c for t in range(n) for c in _pair_copies(
                w_hbms[t], bufs[t], sems.at[t], e, s0, s1, buf)]

        def dzs(f):
            """The pre-activation gradient tiles of reverse slot f, one
            per weight operand."""
            if n == 2:
                cols = pl.ds(rev_ob_ref[i, f] * bs, bs)
                return _gate_dz(*(r[0, :, cols].astype(jnp.float32)
                                  for r in (dy_ref, *res_refs)), dy_ref.dtype)
            ob = rev_ob_ref[i, f]
            dyb = dy_ref[0, :, pl.ds(ob * bs, bs)]
            if not res_refs:
                return (dyb,)
            gr = act_bwd(
                res_refs[0][0, :, pl.ds(ob * bs, bs)].astype(jnp.float32), act)
            return ((dyb.astype(jnp.float32) * gr).astype(dyb.dtype),)

        def _compute():
            _run_copies(copies(0, 0), "start")
            acc = jnp.zeros((bm, bs), jnp.float32)
            for p in range(npair):
                if p + 1 < npair:
                    _run_copies(copies((p + 1) % 2, p + 1), "start")
                _run_copies(copies(p % 2, p), "wait")
                for j in range(min(2, fb - 2 * p)):
                    f = 2 * p + j
                    dz = dzs(f)
                    if n == 1:   # order: the mask before the product
                        acc = acc + jnp.where(
                            f < cnt, _rev_dot(dz[0], bufs[0][p % 2, j]), 0.0)
                    else:
                        part = (_rev_dot(dz[0], bufs[0][p % 2, j])
                                + _rev_dot(dz[1], bufs[1][p % 2, j]))
                        acc = acc + jnp.where(f < cnt, part, 0.0)
            o_ref[0] = acc.astype(o_ref.dtype)

        _run_live(_compute, cnt_ref, bm, 1)

    row = pl.BlockSpec((1, bm, nob * bs), lambda e, m, i, *_: (e, m, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out_specs = [pl.BlockSpec((1, bm, bs), lambda e, m, i, *_: (e, m, i))]
    prefetch = (rev_ob, rev_t, rev_cnt)
    if counts is not None:
        prefetch += (counts,)
    rows = lambda im: _rows_outer(im, bm, nib)
    return pl.pallas_call(
        kernel,
        name=_kernel_name(f"junction_{_gate(ws)}dx", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, M // bm, nib),
            in_specs=_counted_specs([row] * (1 + len(res)) + [hbm] * n,
                                    counts, rows),
            out_specs=_counted_specs(out_specs, counts, rows)[0],
            scratch_shapes=[pltpu.VMEM((2, 2, bs, bs), w.dtype) for w in ws]
            + [pltpu.SemaphoreType.DMA((2, 2) if n == 1 else (n, 2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((E, M, nib * bs), dy.dtype),
        interpret=interpret,
    )(*prefetch, dy, *res, *w_flats)


def dx(dy, w, rev_ob, rev_t, rev_cnt, res, *, act: str = "none",
       bm: int | None = None, interpret: bool = False, counts=None):
    """dy [E, M, nob*bs] -> dx [E, M, nib*bs] against one weight operand
    w [E, nob, kb, bs, bs]; ``res`` the residual ``act`` needs (ignored
    for act "none"): ``junction_dx``."""
    return junction_dx(dy, (w,), rev_ob, rev_t, rev_cnt,
                       () if act == "none" else (res,), act=act, bm=bm,
                       interpret=interpret, counts=counts)


def gated_dx(dh, wg, wi, rev_ob, rev_t, rev_cnt, g, u, *,
             bm: int | None = None, interpret: bool = False, counts=None):
    """The gated FFN's dx: both branch grads (dz_g = dh * u * silu'(g),
    dz_u = dh * silu(g)) recomputed per dy block from the saved
    residuals, both weight streams double-buffered HBM→VMEM in-kernel:
    ``junction_dx`` of two weight operands."""
    return junction_dx(dh, (wg, wi), rev_ob, rev_t, rev_cnt, (g, u), bm=bm,
                       interpret=interpret, counts=counts)


# ------------------------------------------------------------------ dw (+db)
def _grad_specs(bm: int, bs: int, kb: int, n_rows: int):
    """BlockSpecs of a weight-gradient grid (E, nob, M/bm)'s row inputs:
    the ``n_rows`` [bm, bs] tiles of output block o (dy and residuals),
    then the kb gathered input blocks, whose index maps read the pattern
    from scalar prefetch — the interleaver as a DMA descriptor."""
    row = pl.BlockSpec((1, bm, bs), lambda e, o, m, *_: (e, m, o))
    return [row] * n_rows + [
        pl.BlockSpec((1, bm, bs),
                     lambda e, o, m, idx, *_, k=k: (e, m, idx[o, k]))
        for k in range(kb)]


def _zero_grads(accs, accb_ref):
    for acc in accs:
        acc[...] = jnp.zeros(acc.shape, jnp.float32)
    if accb_ref is not None:
        accb_ref[...] = jnp.zeros(accb_ref.shape, jnp.float32)


def _grad_tile(dy_ref, res_refs, x_refs, accs, accb_ref, act):
    """One row tile of the weight-gradient reduction: the pre-activation
    gradient recomputed from the residuals (the activation's, or both gate
    branches' from (g, u) for two weight operands), every fan-in slot's
    ``x^T dz`` added into each operand's fp32 VMEM scratch, and the bias
    gradient into its own."""
    two = len(accs) == 2
    dzf = None
    if two:
        dzs = _gate_dz(*(r[0].astype(jnp.float32)
                         for r in (dy_ref, *res_refs)), dy_ref.dtype)
    elif res_refs:
        grad = act_bwd(res_refs[0][0].astype(jnp.float32), act)
        dzf = dy_ref[0].astype(jnp.float32) * grad
        dzs = (dzf.astype(dy_ref.dtype),)
    else:
        dzs = (dy_ref[0],)
    for k, x_ref in enumerate(x_refs):
        # order: two operands read the x tile once, before both
        # accumulators; one reads it after its accumulator
        shared = x_ref[0].T if two else None
        for acc, dz in zip(accs, dzs):
            acc[k] = acc[k] + jnp.dot(
                x_ref[0].T if shared is None else shared, dz,
                preferred_element_type=jnp.float32)
    if accb_ref is not None:
        s = dzf if dzf is not None else dy_ref[0].astype(jnp.float32)
        accb_ref[...] = accb_ref[...] + jnp.sum(s, axis=0, keepdims=True)


def _grad_bm(M: int, kb: int, bs: int, n_w: int, itemsize: int) -> int:
    """The weight-gradient kernels' default row tile (kb + 3 row blocks
    for one weight operand, kb + 5 for two)."""
    return bwd_bm(M, kb + 1 + 2 * n_w, bs, itemsize)


def junction_dw(x, dy, idx, res, *, act: str = "none",
                with_bias: bool = True, bm: int | None = None,
                interpret: bool = False, counts=None):
    """(dws, db): the weight gradients [E, nob, kb, bs, bs] fp32, one per
    weight operand, and db [E, nob*bs] fp32 (None without ``with_bias``).
    ``res`` holds the forward's residuals as ``junction_dx`` takes them;
    the gate's (g, u) give two weight gradients (and no bias).

    Grid (E, nob, M/bm) with the M reduction innermost into fp32 VMEM
    scratch, flushed once per (unit, output block).  The kb gathered
    input blocks arrive through scalar-prefetch BlockSpec index_maps —
    the interleaver as a DMA descriptor — and, for biased layers, db
    accumulates from the same fused dz prologue.  ``counts``: live rows
    per unit, as in ``junction_fwd`` (dead row tiles add nothing)."""
    E, M, _ = x.shape
    nob, kb = idx.shape
    bs = dy.shape[2] // nob
    n = 2 if len(res) == 2 else 1
    if bm is None:
        bm = _grad_bm(M, kb, bs, n, x.dtype.itemsize)
    assert M % bm == 0
    nm = M // bm
    n_in, n_out = 1 + len(res) + kb, n + with_bias

    def kernel(idx_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        dy_ref, res_refs = refs[0], refs[1:n_in - kb]
        x_refs, outs = refs[n_in - kb:n_in], refs[n_in:n_in + n_out]
        accs = refs[n_in + n_out:]
        accb_ref = accs[n] if with_bias else None
        accs = accs[:n]
        m = pl.program_id(2)

        @pl.when(m == 0)
        def _zero():
            _zero_grads(accs, accb_ref)

        _run_live(lambda: _grad_tile(dy_ref, res_refs, x_refs, accs, accb_ref,
                                     act), cnt_ref, bm, 2)

        @pl.when(m == nm - 1)
        def _flush():
            for o_ref, acc in zip(outs, accs):
                o_ref[...] = acc[...][None, None]
            if with_bias:
                outs[n][0] = accb_ref[...]

    out_specs = [pl.BlockSpec((1, 1, kb, bs, bs),
                              lambda e, o, m, *_: (e, o, 0, 0, 0))] * n
    out_shape = [jax.ShapeDtypeStruct((E, nob, kb, bs, bs), jnp.float32)] * n
    scratch = [pltpu.VMEM((kb, bs, bs), jnp.float32) for _ in range(n)]
    if with_bias:
        out_specs.append(pl.BlockSpec((1, 1, bs),
                                      lambda e, o, m, *_: (e, 0, o)))
        out_shape.append(jax.ShapeDtypeStruct((E, 1, nob * bs), jnp.float32))
        scratch.append(pltpu.VMEM((1, bs), jnp.float32))

    prefetch = (idx,) if counts is None else (idx, counts)
    rows = lambda im: _rows_inner(im, bm)
    outs = pl.pallas_call(
        kernel,
        name=_kernel_name("junction_gated_dw" if n == 2 else "junction_dw",
                          counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, nob, nm),
            in_specs=_counted_specs(_grad_specs(bm, bs, kb, 1 + len(res)),
                                    counts, rows),
            out_specs=_counted_specs(out_specs, counts, rows),
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(*prefetch, dy, *res, *[x] * kb)
    db = outs[n].reshape(E, -1) if with_bias else None
    return tuple(outs[:n]), db


def dw(x, dy, idx, res, *, act: str = "none", with_bias: bool = True,
       bm: int | None = None, interpret: bool = False, counts=None):
    """(dw [E, nob, kb, bs, bs] fp32, db [E, nob*bs] fp32 or None) of one
    weight operand, ``res`` the residual ``act`` needs (ignored for act
    "none"): ``junction_dw``."""
    (dwv,), db = junction_dw(x, dy, idx, () if act == "none" else (res,),
                             act=act, with_bias=with_bias, bm=bm,
                             interpret=interpret, counts=counts)
    return dwv, db


def gated_dw(x, dh, idx, g, u, *, bm: int | None = None,
             interpret: bool = False, counts=None):
    """(dwg, dwi) [E, nob, kb, bs, bs] fp32 for the fused gated FFN, both
    branch grads recomputed in the prologue from the (g, u) residuals:
    ``junction_dw`` of the gate."""
    return junction_dw(x, dh, idx, (g, u), with_bias=False, bm=bm,
                       interpret=interpret, counts=counts)[0]


# --------------------------------------------------- fused BP+UP (update_dw)
# The health detector's output is one int32 lane row per unit, [E, 1, 128]:
# a vector-shaped block the TPU can store to (Mosaic refuses scalar stores
# to VMEM), revisited across every (ob, m) step of unit e.  Every lane of
# the row holds the same count; the wrappers return lane 0 as the [E] flags.
HEALTH_LANES = 128
HEALTH_SPEC = pl.BlockSpec((1, 1, HEALTH_LANES), lambda e, o, m, *_: (e, 0, 0))


def _health_shape(E: int):
    return jax.ShapeDtypeStruct((E, 1, HEALTH_LANES), jnp.int32)


def _flag_unhealthy(health_ref, ok):
    """Count one bad (e, ob) tile into unit e's health row unless ``ok``."""
    health_ref[...] += jnp.where(ok, 0, 1).astype(jnp.int32)


def normalize_hyp(hyp, E: int, *, name: str = "hyp"):
    """Normalize every accepted hyp shape to the canonical ``[E, HYP_K]``
    f32 table: a ``(HYP_K,)`` row broadcasts to all units, and a legacy
    ``(2,)`` / ``[E, 2]`` [lr, momentum] pair pads to
    ``[lr, momentum, 0, 0, 0, 0, 1]`` — bitwise-identical SGD numerics
    (gs=1 is an exact no-op, b2..t are ignored by the SGD branch)."""
    hyp = jnp.asarray(hyp, jnp.float32)
    if hyp.shape in ((2,), (HYP_K,)):
        hyp = jnp.broadcast_to(hyp, (E,) + hyp.shape)
    if hyp.shape == (E, 2):
        hyp = jnp.concatenate(
            [hyp, jnp.zeros((E, HYP_K - 3), jnp.float32),
             jnp.ones((E, 1), jnp.float32)], axis=1)
    if hyp.shape != (E, HYP_K):
        raise ValueError(
            f"{name} must be a (2,) [lr, momentum] pair, a ({HYP_K},) "
            f"[{', '.join(HYP_COLS)}] row, or a per-unit [E={E}, 2] / "
            f"[E={E}, {HYP_K}] table, got {hyp.shape}")
    return hyp


def _decay_power(b, t):
    """``b ** t`` for a decay rate b in [0, 1] and a step count t >= 0, as
    a (1, 1) vector: Mosaic has no scalar or vector ``powf``, so the power
    is ``exp(t * log b)``, with ``pow(b, 0) = 1`` and ``pow(0, t) = 0``
    selected exactly (the all-zero-row freeze relies on the first)."""
    bv = jnp.full((1, 1), b, jnp.float32)
    tv = jnp.full((1, 1), t, jnp.float32)
    p = jnp.exp(tv * jnp.log(jnp.where(bv == 0.0, 1.0, bv)))
    return jnp.where(tv == 0.0, 1.0, jnp.where(bv == 0.0, 0.0, p))


def _epilogue_step(h, acc, w32, mom, vel, with_health):
    """One tile's in-kernel optimizer step from the fp32 gradient
    accumulator ``acc``: SGD(+momentum) when ``vel`` is None, Adam when
    the second accumulator rides along (the static slot switch of the
    module docstring).  ``h(col)`` reads the unit's hyp row; returns
    ``(new_w32, new_mom, new_vel, ok)`` with ``ok`` the tile's isfinite
    health verdict (None unless with_health).

    The Adam guards make an all-zero hyp row an exact freeze: pow(0, 0)
    is 1, so both bias-correction denominators hit the ``c == 0 -> 1``
    guard, and eps=0 makes the update denominator 0, which resolves to a
    zero update — w' = w bitwise.  With real hyperparameters every guard
    predicate is false and the selected values are the reference
    formula's, so parity with ``optim.adam`` is unaffected.  Health
    checks the raw accumulators (m', v'), never the guarded update — a
    ``where`` would mask NaNs (NaN comparisons are false)."""
    g = h(COL_GS) * acc
    if vel is None:
        mv = g if mom is None else h(COL_B1) * mom + g
        new_w32 = w32 - h(COL_LR) * mv
        ok = jnp.all(jnp.isfinite(mv)) if with_health else None
        return new_w32, (mv if mom is not None else None), None, ok
    b1, b2 = h(COL_B1), h(COL_B2)
    m1 = b1 * mom + (1.0 - b1) * g
    v2 = b2 * vel + (1.0 - b2) * jnp.square(g)
    t = h(COL_T)
    c1 = 1.0 - _decay_power(b1, t)
    c2 = 1.0 - _decay_power(b2, t)
    c1 = jnp.where(c1 == 0.0, 1.0, c1)
    c2 = jnp.where(c2 == 0.0, 1.0, c2)
    den = jnp.sqrt(v2 / c2) + h(COL_EPS)
    upd = jnp.where(den == 0.0, 0.0, (m1 / c1) / den)
    upd = upd + h(COL_WD) * w32
    new_w32 = w32 - h(COL_LR) * upd
    ok = (jnp.logical_and(jnp.all(jnp.isfinite(m1)),
                          jnp.all(jnp.isfinite(v2)))
          if with_health else None)
    return new_w32, m1, v2, ok


def _apply_updates(h, accs, ins, outs, ix, with_health):
    """The flush epilogue's optimizer step for one family of parameter
    tiles (a junction's weight operands, or its bias): ``ins``/``outs``
    hold each tensor's (param, slots...) refs, read and written at block
    index ``ix``.  Every tensor's step first, then the slots' writes, then
    the parameters'.  Returns the tiles' health verdicts."""
    steps = [_epilogue_step(h, acc[...], p[0][ix].astype(jnp.float32),
                            p[1][ix] if len(p) > 1 else None,
                            p[2][ix] if len(p) > 2 else None, with_health)
             for acc, p in zip(accs, ins)]
    for s in (1, 2):
        for st, o in zip(steps, outs):
            if len(o) > s:
                o[s][ix] = st[s]
    for st, o in zip(steps, outs):
        o[0][ix] = st[0].astype(o[0].dtype)
    return [st[3] for st in steps]


def junction_update(x, dy, idx, res, ws, moms, vels, b, mom_b, vel_b, hyp, *,
                    act: str = "none", bm: int | None = None,
                    with_health: bool = False, interpret: bool = False,
                    counts=None):
    """The fused UP stage for weight operands ``ws``: the ``junction_dw``
    gradient reduction with the optimizer update applied in the flush
    epilogue, every parameter operand aliased to its output
    (``input_output_aliases``), so the weight gradient never leaves VMEM
    scratch and the parameters are rewritten in place.  ``moms``/``vels``
    are the accumulator slots mirroring ws (both empty: SGD; moms alone:
    SGD+momentum; both: Adam m, v), all fp32; ``b`` the bias (None
    without) with its slots ``mom_b``/``vel_b`` (None where absent).
    Returns ``(new_ws, new_b, new_moms, new_mom_b, new_vels, new_vel_b,
    health)``: () for absent weight slots, None for an absent bias, bias
    slot or health output.

    Same grid, BlockSpecs and default row tile as ``junction_dw``, so the
    fp32 accumulation order matches the two-pass path exactly (parity to
    fp32 round-off).  hyp is the per-unit ``[E, HYP_K]`` table of the
    module docstring's column registry (any shape ``normalize_hyp``
    accepts), row ``e = program_id(0)`` read in the epilogue.

    ``with_health=True`` adds a tiny non-aliased ``[E]`` int32 output
    riding the same flush: each (e, ob) epilogue OR-reduces ``isfinite``
    over the accumulator tiles it just wrote (m and v for Adam, every
    weight operand, and the bias update) and accumulates one count into
    unit e's slot — the in-kernel divergence detector.  health[e] > 0
    means unit e wrote at least one non-finite parameter tile this step.

    ``counts``: live rows per unit, as in ``junction_dw``; every (unit,
    output block) is still updated, a unit with no live row from its
    moments."""
    E, M, _ = x.shape
    nob, kb = idx.shape
    bs = dy.shape[2] // nob
    n = len(ws)
    with_bias = b is not None
    hyp = normalize_hyp(hyp, E)
    if bm is None:
        bm = _grad_bm(M, kb, bs, n, x.dtype.itemsize)
    assert M % bm == 0
    nm = M // bm
    # the parameter operands, each aliased to the output at its position:
    # every weight operand, then their m slots, then their v slots; then
    # the bias and its slots
    per_w = 1 + len(moms) // n + len(vels) // n
    params = [*ws, *moms, *vels]
    if with_bias:
        params += [a.reshape(E, 1, -1)
                   for a in (b, mom_b, vel_b)[:per_w]]
    n_p = len(params)
    n_in = 1 + len(res) + kb

    def tensors(refs, t):
        """The (param, slots...) refs of weight operand t; t == n: the
        bias's."""
        if t == n:
            return refs[n * per_w:]
        return refs[t:n * per_w:n]

    def kernel(idx_ref, hyp_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        dy_ref, res_refs, x_refs = (refs[0], refs[1:n_in - kb],
                                    refs[n_in - kb:n_in])
        ins, outs = refs[n_in:n_in + n_p], refs[n_in + n_p:n_in + 2 * n_p]
        health_ref = refs[n_in + 2 * n_p] if with_health else None
        accs = refs[n_in + 2 * n_p + with_health:]
        accb_ref = accs[n] if with_bias else None
        accs = accs[:n]
        e = pl.program_id(0)
        o = pl.program_id(1)
        m = pl.program_id(2)

        @pl.when(m == 0)
        def _zero():
            _zero_grads(accs, accb_ref)

        if with_health:
            # health slot e is revisited across every (o, m) step: init once
            @pl.when(jnp.logical_and(o == 0, m == 0))
            def _zero_health():
                health_ref[...] = jnp.zeros(health_ref.shape, jnp.int32)

        _run_live(lambda: _grad_tile(dy_ref, res_refs, x_refs, accs, accb_ref,
                                     act), cnt_ref, bm, 2)

        @pl.when(m == nm - 1)
        def _apply():
            def h(col):
                return hyp_ref[e, col]

            oks = _apply_updates(h, accs, [tensors(ins, t) for t in range(n)],
                                 [tensors(outs, t) for t in range(n)],
                                 (0, 0), with_health)
            if with_bias:
                oks += _apply_updates(h, [accb_ref], [tensors(ins, n)],
                                      [tensors(outs, n)], (0,), with_health)
            if with_health:
                _flag_unhealthy(health_ref, functools.reduce(jnp.logical_and,
                                                             oks))

    wspec = pl.BlockSpec((1, 1, kb, bs, bs),
                         lambda e, o, m, *_: (e, o, 0, 0, 0))
    # per-unit bias operands ride as [E, 1, N]: the block's last two dims
    # (1, bs) then equal / tile the array's for every E
    bspec = pl.BlockSpec((1, 1, bs), lambda e, o, m, *_: (e, 0, o))
    param_specs = [wspec] * (n * per_w) + [bspec] * (n_p - n * per_w)
    out_specs = param_specs + ([HEALTH_SPEC] if with_health else [])
    out_shape = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in params]
    if with_health:
        out_shape.append(_health_shape(E))
    scratch = [pltpu.VMEM((kb, bs, bs), jnp.float32) for _ in ws]
    if with_bias:
        scratch.append(pltpu.VMEM((1, bs), jnp.float32))

    prefetch = (idx, hyp) if counts is None else (idx, hyp, counts)
    aliases = {len(prefetch) + n_in + i: i for i in range(n_p)}
    rows = lambda im: _rows_inner(im, bm)
    outs = pl.pallas_call(
        kernel,
        name=_kernel_name(f"junction_update_{_gate(ws)}dw", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, nob, nm),
            in_specs=_counted_specs(
                _grad_specs(bm, bs, kb, 1 + len(res)) + param_specs, counts,
                rows),
            out_specs=_counted_specs(out_specs, counts, rows),
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*prefetch, dy, *res, *[x] * kb, *params)
    groups = [tuple(outs[s * n:(s + 1) * n]) for s in range(per_w)]
    groups += [()] * (3 - per_w)
    bias_outs = [a.reshape(E, -1) for a in outs[n * per_w:n_p]]
    bias_outs += [None] * (3 - len(bias_outs))
    health = outs[n_p][:, 0, 0] if with_health else None
    return (groups[0], bias_outs[0], groups[1], bias_outs[1], groups[2],
            bias_outs[2], health)


def update_dw(x, dy, idx, res, w, b, mom, mom_b, hyp, *, vel=None,
              vel_b=None, act: str = "none", with_bias: bool = True,
              bm: int | None = None, with_health: bool = False,
              interpret: bool = False, counts=None):
    """The fused UP stage of one weight operand (``junction_update``):
    returns ``(new_w, new_b, new_mom, new_mom_b, new_vel, new_vel_b,
    health)``, None where the operand is absent.  The accumulator slots
    select the optimizer statically: mom/mom_b alone → SGD(+momentum),
    plus vel/vel_b → Adam (m, v); ``res`` is the residual ``act`` needs
    (ignored for act "none")."""
    assert vel is None or mom is not None, \
        "Adam (vel) requires the mom slot too"
    assert not (vel is not None and with_bias) or vel_b is not None
    one = lambda a: () if a is None else (a,)
    (nw,), nb, nm, nmb, nv, nvb, health = junction_update(
        x, dy, idx, () if act == "none" else (res,), (w,), one(mom),
        one(vel), b if with_bias else None, mom_b, vel_b, hyp, act=act,
        bm=bm, with_health=with_health, interpret=interpret, counts=counts)
    return (nw, nb, nm[0] if nm else None, nmb, nv[0] if nv else None, nvb,
            health)


def update_gated_dw(x, dh, idx, g, u, wg, wi, mg, mi, hyp, *, vg=None,
                    vi=None, bm: int | None = None,
                    with_health: bool = False, interpret: bool = False,
                    counts=None):
    """Fused BP+UP for the gated junction (``junction_update`` of two
    weight operands, no bias): both branch gradients reduce into VMEM
    scratch as in ``gated_dw`` and the flush epilogue updates BOTH
    weight streams in place — returns ``(new_wg, new_wi, new_mg,
    new_mi, new_vg, new_vi, health)`` (absent slots None); mg/mi alone
    → SGD(+momentum), plus vg/vi → Adam."""
    assert vg is None or (mg is not None and vi is not None), \
        "Adam (vg/vi) requires the mg/mi slots too"
    nws, _, nms, _, nvs, _, health = junction_update(
        x, dh, idx, (g, u), (wg, wi), () if mg is None else (mg, mi),
        () if vg is None else (vg, vi), None, None, None, hyp, bm=bm,
        with_health=with_health, interpret=interpret, counts=counts)
    return (*nws, *(nms or (None, None)), *(nvs or (None, None)), health)
