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
* **update_dw / update_gated_dw** — the fused **BP+UP** variants (the
  paper's concurrent backprop + update pipeline): same grid and the same
  M-innermost VMEM-scratch gradient reduction as ``dw``/``gated_dw``,
  but instead of flushing the weight gradient to HBM the flush epilogue
  applies the optimizer update **in-kernel** on the last M step.  The
  optimizer is a STATIC switch keyed on which fp32 accumulator slots
  ride along (``_epilogue_step``): momentum-only runs SGD(+momentum),
  a second (m, v) slot pair runs Adam with per-step bias correction and
  decoupled weight decay — the hyperparameters come from the per-unit
  ``[E, HYP_K]`` hyp table in scalar prefetch (registry below).
  Every parameter and accumulator operand comes in as a per-(e, ob)
  resident tile and leaves as an output declared with
  ``input_output_aliases``, so XLA rewrites the buffers in place —
  neither ``dw`` nor a second copy of ``w`` ever touches HBM.  The
  aliasing contract: every parameter operand maps to the output at
  the same relative position, the input/output BlockSpecs are identical,
  and each (e, ob) tile is read and written exactly once (the M loop is
  innermost), so no grid step can observe a partially-updated tile.
  Accumulator slots are fp32 even for bf16 params.

  With ``with_health=True`` the update kernels additionally emit a tiny
  **non-aliased** ``[E, 1, 128]`` int32 health output — the in-kernel
  divergence detector.  Because the in-place update means a non-finite
  ``dw`` silently destroys the parameter state (there is no HBM gradient
  to inspect downstream), the flush epilogue OR-reduces ``isfinite``
  over each post-momentum update tile (both branches for the gated
  kernel, plus the bias update for biased layers) and accumulates a
  per-unit count of bad (e, ob) tiles: ``health[e] > 0`` ⇔ unit e wrote
  at least one non-finite parameter tile this step.  The slot is a
  single revisited ``(1, 1, 128)`` lane row per unit (zeroed at the
  first (ob, m) step, written only at flushes; a vector block because the
  TPU cannot store a scalar to VMEM) — one VMEM compare per tile,
  no gradient materialization, and the parameter outputs' aliasing
  contract is untouched.  ``ops.junction_train_update`` surfaces it as
  the cotangent of a dummy ``[E]`` health operand; ``train/steps.py``
  aggregates it into ``metrics["nonfinite"]``.
* **gated_{fwd,dx,dw}** — the GShard/SwiGLU gate
  ``silu(x @ Wg) * (x @ Wi)`` fused into single passes: both fan-in
  reductions accumulate side by side in VMEM scratch in the forward, and
  the backward kernels recompute both branch gradients
  (``dz_g = dh * u * silu'(g)``, ``dz_u = dh * silu(g)``) from the saved
  ``(g, u)`` residuals, ``gated_dx`` double-buffering BOTH reverse
  weight streams.

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

``fwd_int8`` / ``gated_fwd_int8`` / ``fwd_fxp`` are forward-only twins
of ``fwd``/``gated_fwd`` for post-training-quantized weights
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
* **gated_fwd_int8** — both expert branches dotted in int8 with
  per-branch scale prefetch leaves, shared in-body activation codes,
  two fp32 scratch accumulators, ``silu(g) * u`` epilogue unchanged.

Tile tuning — one table for every configuration
-----------------------------------------------

``TUNE_TABLE`` maps a canonical 6-key

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

To add a measured entry: run ``benchmarks/run.py --json`` on real
hardware, pick the winning tiles for an ``engine.*`` row, and add the
key to ``_SEED_ENTRIES`` below.  Legacy key schemas keep working —
``canonical_tune_key`` migrates PR 1's 4-key ``(M, nob, kb, bs)`` and
the transitional 5-key ``(E, M, nob, kb, bs)`` by pinning the missing
dims to ``E=1`` / ``n_weight_operands=1`` — so entries derived from old
``BENCH_*.json`` artifacts can be pasted in their original form.
Misses fall back to a VMEM-budget heuristic (``choose_tiles``).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128

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


def canonical_tune_key(key) -> tuple[int, int, int, int, int, int]:
    """Normalize a tune-table key to the canonical 6-tuple
    ``(E, M, nob, kb, bs, n_weight_operands)``.

    Migration shim for pre-unification schemas: PR 1 keyed single-junction
    entries ``(M, nob, kb, bs)`` (implicitly E=1, one weight operand) and
    PR 2 keyed expert entries ``(E, M, nob, kb, bs, n_weight_operands)``;
    a transitional 5-key ``(E, M, nob, kb, bs)`` pins one weight operand.
    """
    key = tuple(int(v) for v in key)
    if len(key) == 4:        # PR 1: (M, nob, kb, bs)
        return (1, *key, 1)
    if len(key) == 5:        # transitional: (E, M, nob, kb, bs)
        return (*key, 1)
    if len(key) == 6:        # canonical (PR 2 expert schema)
        return key
    raise ValueError(f"tune key {key!r}: expected 4, 5 or 6 ints")


# Measured entries (BENCH_*.json artifacts are the data source).  Keys may
# be written in any historical schema — canonical_tune_key migrates them.
_SEED_ENTRIES: dict[tuple, tuple[int, int]] = {
    # PR 1, paper MNIST junction (12544-sample epoch, 1024->512 @ kb=2)
    (12544, 4, 2, 128): (512, 4),
    # PR 1, transformer FFN up-projection bench shape (1024->4096 @ kb=2)
    (4096, 32, 2, 128): (256, 8),
    # PR 2, engine.moe bench gated entry kernel: E=4 experts, top-2 routed
    # 2048 tokens (capacity rows M=1280), 1024->512 @ kb=2, two weight
    # operands (wg + wi streamed per step)
    (4, 1280, 4, 2, 128, 2): (256, 4),
}

TUNE_TABLE: dict[tuple[int, int, int, int, int, int], tuple[int, int]] = {
    canonical_tune_key(k): v for k, v in _SEED_ENTRIES.items()
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
    TUNE_TABLE first (canonical 6-key, legacy keys migrated), then the
    VMEM heuristic — bm bounded by the resident x row block (one expert's
    row block is resident per grid step, so the bound is E-independent),
    bn the largest power-of-two divisor of nob whose weight bundle fits
    the per-step budget split across the streamed weight tensors."""
    hit = TUNE_TABLE.get(canonical_tune_key((E, M, nob, kb, bs,
                                             n_weight_operands)))
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
def fwd(x, w, idx, bias, *, act: str = "none", bm: int | None = None,
        bn: int | None = None, save_pre: bool = False,
        interpret: bool = False, counts=None):
    """x [E, M, nib*bs], w [E, nob, kb, bs, bs], shared idx [nob, kb],
    bias [E, nob*bs] -> act(x_e @ W_e + b_e) [E, M, nob*bs] per junction
    unit (+ pre-activation if save_pre).

    Grid (E, M/bm, nob/bn): the expert dimension is the outermost grid
    axis; the pattern rides once in scalar prefetch and is reused by every
    unit.  One step computes bn output tiles — the kb fan-in slots reduce
    in-body into fp32 VMEM scratch, epilogue fused, single output write.
    ``counts`` [E] int32: live rows per unit (see "live row counts")."""
    E, M, _ = x.shape
    _, nob, kb, bs, _ = w.shape
    nib = x.shape[2] // bs
    cbm, cbn = choose_tiles(M, nob, kb, bs, nib, x.dtype.itemsize, E=E)
    bm = cbm if bm is None else bm
    bn = cbn if bn is None else bn
    if nob % bn:
        bn = 1
    assert M % bm == 0, f"M={M} must be a multiple of bm={bm} (pad in ops.py)"

    def fwd_kernel(idx_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        x_ref, w_ref, b_ref, *rest = refs
        acc_ref = rest[-1]
        o_ref = rest[0]
        ob0 = pl.program_id(2) * bn

        def _compute():
            for j in range(bn):
                acc = jnp.zeros((bm, bs), jnp.float32)
                for k in range(kb):
                    ib = idx_ref[ob0 + j, k]
                    xk = x_ref[0, :, pl.ds(ib * bs, bs)]
                    acc = acc + jnp.dot(xk, w_ref[0, j, k],
                                        preferred_element_type=jnp.float32)
                acc_ref[:, j * bs:(j + 1) * bs] = acc
            s = acc_ref[...] + b_ref[0].astype(jnp.float32)
            if save_pre:
                rest[1][0] = s.astype(rest[1].dtype)
            o_ref[0] = act_fwd(s, act).astype(o_ref.dtype)

        _run_live(_compute, cnt_ref, bm, 1)

    out_shape = [jax.ShapeDtypeStruct((E, M, nob * bs), x.dtype)]
    out_specs = [pl.BlockSpec((1, bm, bn * bs), lambda e, m, o, idx: (e, m, o))]
    if save_pre:
        out_shape.append(jax.ShapeDtypeStruct((E, M, nob * bs), x.dtype))
        out_specs.append(pl.BlockSpec((1, bm, bn * bs),
                                      lambda e, m, o, idx: (e, m, o)))

    in_specs = [
        # full activation row block, resident across bundle steps
        pl.BlockSpec((1, bm, nib * bs), lambda e, m, o, idx: (e, m, 0)),
        pl.BlockSpec((1, bn, kb, bs, bs),
                     lambda e, m, o, idx: (e, o, 0, 0, 0)),
        pl.BlockSpec((1, 1, bn * bs), lambda e, m, o, idx: (e, 0, o)),
    ]
    prefetch = (idx,) if counts is None else (idx, counts)
    rows = lambda im: _rows_outer(im, bm, nob // bn)
    outs = pl.pallas_call(
        fwd_kernel,
        name=_kernel_name("junction_fwd", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, M // bm, nob // bn),
            in_specs=_counted_specs(in_specs, counts, rows),
            out_specs=_counted_specs(out_specs, counts, rows),
            scratch_shapes=[pltpu.VMEM((bm, bn * bs), jnp.float32)],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(*prefetch, x, w, bias.reshape(E, 1, -1))
    return (outs[0], outs[1]) if save_pre else (outs[0], None)


def gated_fwd(x, wg, wi, idx, *, bm: int | None = None,
              bn: int | None = None, save_res: bool = False,
              interpret: bool = False, counts=None):
    """Fused SiLU-gate FFN entry: silu(x_e @ Wg_e) * (x_e @ Wi_e) in one
    pass — both kb fan-in reductions accumulate side by side in VMEM
    scratch, the gate epilogue fuses before the single output write.
    Returns (h, g_pre, u) — the pre-activation g and the linear branch u
    are emitted only when save_res (backward residuals).  ``counts``: live
    rows per unit, as in ``fwd``."""
    E, M, _ = x.shape
    _, nob, kb, bs, _ = wg.shape
    nib = x.shape[2] // bs
    cbm, cbn = choose_tiles(M, nob, kb, bs, nib, x.dtype.itemsize, E=E,
                            n_weight_operands=2)
    bm = cbm if bm is None else bm
    bn = cbn if bn is None else bn
    if nob % bn:
        bn = 1
    assert M % bm == 0, f"M={M} must be a multiple of bm={bm} (pad in ops.py)"

    def gated_fwd_kernel(idx_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        x_ref, wg_ref, wi_ref, *rest = refs
        accg_ref, accu_ref = rest[-2], rest[-1]
        h_ref = rest[0]
        ob0 = pl.program_id(2) * bn

        def _compute():
            for j in range(bn):
                ag = jnp.zeros((bm, bs), jnp.float32)
                au = jnp.zeros((bm, bs), jnp.float32)
                for k in range(kb):
                    ib = idx_ref[ob0 + j, k]
                    xk = x_ref[0, :, pl.ds(ib * bs, bs)]
                    ag = ag + jnp.dot(xk, wg_ref[0, j, k],
                                      preferred_element_type=jnp.float32)
                    au = au + jnp.dot(xk, wi_ref[0, j, k],
                                      preferred_element_type=jnp.float32)
                accg_ref[:, j * bs:(j + 1) * bs] = ag
                accu_ref[:, j * bs:(j + 1) * bs] = au
            g = accg_ref[...]
            u = accu_ref[...]
            if save_res:
                rest[1][0] = g.astype(rest[1].dtype)
                rest[2][0] = u.astype(rest[2].dtype)
            h_ref[0] = (act_fwd(g, "silu") * u).astype(h_ref.dtype)

        _run_live(_compute, cnt_ref, bm, 1)

    out_shape = [jax.ShapeDtypeStruct((E, M, nob * bs), x.dtype)]
    out_specs = [pl.BlockSpec((1, bm, bn * bs), lambda e, m, o, idx: (e, m, o))]
    if save_res:
        for _ in range(2):
            out_shape.append(jax.ShapeDtypeStruct((E, M, nob * bs), x.dtype))
            out_specs.append(pl.BlockSpec((1, bm, bn * bs),
                                          lambda e, m, o, idx: (e, m, o)))

    in_specs = [
        pl.BlockSpec((1, bm, nib * bs), lambda e, m, o, idx: (e, m, 0)),
        pl.BlockSpec((1, bn, kb, bs, bs),
                     lambda e, m, o, idx: (e, o, 0, 0, 0)),
        pl.BlockSpec((1, bn, kb, bs, bs),
                     lambda e, m, o, idx: (e, o, 0, 0, 0)),
    ]
    prefetch = (idx,) if counts is None else (idx, counts)
    rows = lambda im: _rows_outer(im, bm, nob // bn)
    outs = pl.pallas_call(
        gated_fwd_kernel,
        name=_kernel_name("junction_gated_fwd", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, M // bm, nob // bn),
            in_specs=_counted_specs(in_specs, counts, rows),
            out_specs=_counted_specs(out_specs, counts, rows),
            scratch_shapes=[pltpu.VMEM((bm, bn * bs), jnp.float32),
                            pltpu.VMEM((bm, bn * bs), jnp.float32)],
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(*prefetch, x, wg, wi)
    return (outs[0], outs[1], outs[2]) if save_res else (outs[0], None, None)


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


def fwd_int8(x, wq, idx, w_scale, bias, *, act: str = "none",
             x_scale=None, bm: int | None = None, bn: int | None = None,
             interpret: bool = False):
    """int8 forward: x [E, M, nib*bs] fp, wq [E, nob, kb, bs, bs] int8,
    shared idx [nob, kb], w_scale [E, nob, kb] f32 on scalar prefetch,
    bias [E, nob*bs] -> act(dequant(xq @ wq) + b) [E, M, nob*bs].
    Optional x_scale [E] f32 switches activation quantization from
    dynamic per-row to calibrated static per-unit."""
    E, M, _ = x.shape
    _, nob, kb, bs, _ = wq.shape
    nib = x.shape[2] // bs
    cbm, cbn = choose_tiles(M, nob, kb, bs, nib, x.dtype.itemsize, E=E)
    bm = cbm if bm is None else bm
    bn = cbn if bn is None else bn
    if nob % bn:
        bn = 1
    assert M % bm == 0, f"M={M} must be a multiple of bm={bm} (pad in ops.py)"
    has_xs = x_scale is not None

    def fwd_int8_kernel(*refs):
        if has_xs:
            idx_ref, sc_ref, xs_ref, x_ref, w_ref, b_ref, o_ref, acc_ref = refs
        else:
            idx_ref, sc_ref, x_ref, w_ref, b_ref, o_ref, acc_ref = refs
        e = pl.program_id(0)
        ob0 = pl.program_id(2) * bn
        for j in range(bn):
            acc = jnp.zeros((bm, bs), jnp.float32)
            for k in range(kb):
                ib = idx_ref[ob0 + j, k]
                xk = x_ref[0, :, pl.ds(ib * bs, bs)].astype(jnp.float32)
                sx = _slot_x_scale(xk, xs_ref[e] if has_xs else None)
                xq = jnp.clip(jnp.round(xk / sx), -127, 127
                              ).astype(jnp.int8)
                p = jnp.dot(xq, w_ref[0, j, k],
                            preferred_element_type=jnp.int32)
                # dequant into the fp32 reduction slot; grouping matches
                # the jnp sim exactly (see core/quantize._int8_apply)
                acc = acc + p.astype(jnp.float32) * (
                    sx * sc_ref[e, ob0 + j, k])
            acc_ref[:, j * bs:(j + 1) * bs] = acc
        s = acc_ref[...] + b_ref[0].astype(jnp.float32)
        o_ref[0] = act_fwd(s, act).astype(o_ref.dtype)

    prefetch = (idx, w_scale) + ((x_scale,) if has_xs else ())
    out = pl.pallas_call(
        fwd_int8_kernel,
        name="junction_fwd_int8",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, M // bm, nob // bn),
            in_specs=[
                pl.BlockSpec((1, bm, nib * bs), lambda e, m, o, *_: (e, m, 0)),
                pl.BlockSpec((1, bn, kb, bs, bs),
                             lambda e, m, o, *_: (e, o, 0, 0, 0)),
                pl.BlockSpec((1, 1, bn * bs), lambda e, m, o, *_: (e, 0, o)),
            ],
            out_specs=[pl.BlockSpec((1, bm, bn * bs),
                                    lambda e, m, o, *_: (e, m, o))],
            scratch_shapes=[pltpu.VMEM((bm, bn * bs), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((E, M, nob * bs), x.dtype)],
        interpret=interpret,
    )(*prefetch, x, wq, bias.reshape(E, 1, -1))
    return out[0]


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


def gated_fwd_int8(x, wgq, wiq, idx, wg_scale, wi_scale, *, x_scale=None,
                   bm: int | None = None, bn: int | None = None,
                   interpret: bool = False):
    """int8 twin of gated_fwd: silu(dequant(xq @ wgq)) * dequant(xq @
    wiq) — shared in-body activation codes, per-branch scale prefetch
    leaves [E, nob, kb], two fp32 scratch accumulators."""
    E, M, _ = x.shape
    _, nob, kb, bs, _ = wgq.shape
    nib = x.shape[2] // bs
    cbm, cbn = choose_tiles(M, nob, kb, bs, nib, x.dtype.itemsize, E=E,
                            n_weight_operands=2)
    bm = cbm if bm is None else bm
    bn = cbn if bn is None else bn
    if nob % bn:
        bn = 1
    assert M % bm == 0, f"M={M} must be a multiple of bm={bm} (pad in ops.py)"
    has_xs = x_scale is not None

    def gated_fwd_int8_kernel(*refs):
        if has_xs:
            (idx_ref, scg_ref, sci_ref, xs_ref, x_ref, wg_ref, wi_ref,
             h_ref, accg_ref, accu_ref) = refs
        else:
            (idx_ref, scg_ref, sci_ref, x_ref, wg_ref, wi_ref,
             h_ref, accg_ref, accu_ref) = refs
        e = pl.program_id(0)
        ob0 = pl.program_id(2) * bn
        for j in range(bn):
            ag = jnp.zeros((bm, bs), jnp.float32)
            au = jnp.zeros((bm, bs), jnp.float32)
            for k in range(kb):
                ib = idx_ref[ob0 + j, k]
                xk = x_ref[0, :, pl.ds(ib * bs, bs)].astype(jnp.float32)
                sx = _slot_x_scale(xk, xs_ref[e] if has_xs else None)
                xq = jnp.clip(jnp.round(xk / sx), -127, 127
                              ).astype(jnp.int8)
                pg = jnp.dot(xq, wg_ref[0, j, k],
                             preferred_element_type=jnp.int32)
                pu = jnp.dot(xq, wi_ref[0, j, k],
                             preferred_element_type=jnp.int32)
                ag = ag + pg.astype(jnp.float32) * (
                    sx * scg_ref[e, ob0 + j, k])
                au = au + pu.astype(jnp.float32) * (
                    sx * sci_ref[e, ob0 + j, k])
            accg_ref[:, j * bs:(j + 1) * bs] = ag
            accu_ref[:, j * bs:(j + 1) * bs] = au
        g = accg_ref[...]
        u = accu_ref[...]
        h_ref[0] = (act_fwd(g, "silu") * u).astype(h_ref.dtype)

    prefetch = (idx, wg_scale, wi_scale) + ((x_scale,) if has_xs else ())
    out = pl.pallas_call(
        gated_fwd_int8_kernel,
        name="junction_gated_fwd_int8",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, M // bm, nob // bn),
            in_specs=[
                pl.BlockSpec((1, bm, nib * bs), lambda e, m, o, *_: (e, m, 0)),
                pl.BlockSpec((1, bn, kb, bs, bs),
                             lambda e, m, o, *_: (e, o, 0, 0, 0)),
                pl.BlockSpec((1, bn, kb, bs, bs),
                             lambda e, m, o, *_: (e, o, 0, 0, 0)),
            ],
            out_specs=[pl.BlockSpec((1, bm, bn * bs),
                                    lambda e, m, o, *_: (e, m, o))],
            scratch_shapes=[pltpu.VMEM((bm, bn * bs), jnp.float32),
                            pltpu.VMEM((bm, bn * bs), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((E, M, nob * bs), x.dtype)],
        interpret=interpret,
    )(*prefetch, x, wgq, wiq)
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


def dx(dy, w, rev_ob, rev_t, rev_cnt, res, *, act: str = "none",
       bm: int | None = None, interpret: bool = False, counts=None):
    """dy [E, M, nob*bs] -> dx [E, M, nib*bs] via the shared reverse
    (fan-out) pattern against the forward-layout weights w
    [E, nob, kb, bs, bs].

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
    gradient is recomputed per dy block from the residual.  ``counts``:
    live rows per unit, as in ``fwd``."""
    E, M, _ = dy.shape
    _, nob, kb, bs, _ = w.shape
    nib, fb = rev_ob.shape
    has_res = act != "none"
    if bm is None:
        bm = bwd_bm(M, nob * (2 if has_res else 1), bs, dy.dtype.itemsize)
    assert M % bm == 0
    npair = (fb + 1) // 2
    w_flat = w.reshape(E, nob * kb, bs, bs)

    def dx_kernel(rev_ob_ref, rev_t_ref, rev_cnt_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        if has_res:
            dy_ref, res_ref, w_hbm, o_ref, wbuf, sems = refs
        else:
            dy_ref, w_hbm, o_ref, wbuf, sems = refs
            res_ref = None
        e = pl.program_id(0)
        i = pl.program_id(2)
        cnt = rev_cnt_ref[i]

        def slot(f):
            return rev_ob_ref[i, f] * kb + rev_t_ref[i, f]

        def copies(buf, p):
            f0 = 2 * p
            s1 = slot(f0 + 1) if f0 + 1 < fb else None
            return _pair_copies(w_hbm, wbuf, sems, e, slot(f0), s1, buf)

        def _compute():
            _run_copies(copies(0, 0), "start")
            acc = jnp.zeros((bm, bs), jnp.float32)
            for p in range(npair):
                if p + 1 < npair:
                    _run_copies(copies((p + 1) % 2, p + 1), "start")
                _run_copies(copies(p % 2, p), "wait")
                for j in range(min(2, fb - 2 * p)):
                    f = 2 * p + j
                    ob = rev_ob_ref[i, f]
                    dyb = dy_ref[0, :, pl.ds(ob * bs, bs)]
                    if has_res:
                        gr = act_bwd(
                            res_ref[0, :, pl.ds(ob * bs, bs)].astype(jnp.float32),
                            act)
                        dz = (dyb.astype(jnp.float32) * gr).astype(dyb.dtype)
                    else:
                        dz = dyb
                    acc = acc + jnp.where(f < cnt,
                                          _rev_dot(dz, wbuf[p % 2, j]), 0.0)
            o_ref[0] = acc.astype(o_ref.dtype)

        _run_live(_compute, cnt_ref, bm, 1)

    in_specs = [pl.BlockSpec((1, bm, nob * bs),
                             lambda e, m, i, *_: (e, m, 0))]
    inputs = [dy]
    if has_res:
        in_specs.append(pl.BlockSpec((1, bm, nob * bs),
                                     lambda e, m, i, *_: (e, m, 0)))
        inputs.append(res)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    inputs.append(w_flat)

    out_specs = [pl.BlockSpec((1, bm, bs), lambda e, m, i, *_: (e, m, i))]
    prefetch = (rev_ob, rev_t, rev_cnt)
    if counts is not None:
        prefetch += (counts,)
    rows = lambda im: _rows_outer(im, bm, nib)
    return pl.pallas_call(
        dx_kernel,
        name=_kernel_name("junction_dx", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, M // bm, nib),
            in_specs=_counted_specs(in_specs, counts, rows),
            out_specs=_counted_specs(out_specs, counts, rows)[0],
            scratch_shapes=[pltpu.VMEM((2, 2, bs, bs), w.dtype),
                            pltpu.SemaphoreType.DMA((2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((E, M, nib * bs), dy.dtype),
        interpret=interpret,
    )(*prefetch, *inputs)


def gated_dx(dh, wg, wi, rev_ob, rev_t, rev_cnt, g, u, *,
             bm: int | None = None, interpret: bool = False, counts=None):
    """Fused two-branch dx for the gated FFN: both branch grads
    (dz_g = dh * u * silu'(g), dz_u = dh * silu(g)) are recomputed per dy
    block from the saved residuals and reduced against their reverse
    bundles in the same fb loop — one pass over dh/g/u per input block,
    with BOTH weight streams double-buffered HBM→VMEM in-kernel and the
    same pairwise contiguous-run descriptor coalescing as ``dx``.
    ``counts``: live rows per unit, as in ``fwd``."""
    E, M, _ = dh.shape
    _, nob, kb, bs, _ = wg.shape
    nib, fb = rev_ob.shape
    if bm is None:
        bm = bwd_bm(M, 3 * nob, bs, dh.dtype.itemsize)
    assert M % bm == 0
    npair = (fb + 1) // 2
    wg_flat = wg.reshape(E, nob * kb, bs, bs)
    wi_flat = wi.reshape(E, nob * kb, bs, bs)

    def gated_dx_kernel(rev_ob_ref, rev_t_ref, rev_cnt_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        (dh_ref, g_ref, u_ref, wg_hbm, wi_hbm, o_ref, wgbuf, wibuf,
         sems) = refs
        e = pl.program_id(0)
        i = pl.program_id(2)
        cnt = rev_cnt_ref[i]

        def slot(f):
            return rev_ob_ref[i, f] * kb + rev_t_ref[i, f]

        def copies(buf, p):
            f0 = 2 * p
            s0 = slot(f0)
            s1 = slot(f0 + 1) if f0 + 1 < fb else None
            return (_pair_copies(wg_hbm, wgbuf, sems.at[0], e, s0, s1, buf)
                    + _pair_copies(wi_hbm, wibuf, sems.at[1], e, s0, s1, buf))

        def _compute():
            _run_copies(copies(0, 0), "start")
            acc = jnp.zeros((bm, bs), jnp.float32)
            for p in range(npair):
                if p + 1 < npair:
                    _run_copies(copies((p + 1) % 2, p + 1), "start")
                _run_copies(copies(p % 2, p), "wait")
                for j in range(min(2, fb - 2 * p)):
                    f = 2 * p + j
                    cols = pl.ds(rev_ob_ref[i, f] * bs, bs)
                    dhb = dh_ref[0, :, cols].astype(jnp.float32)
                    gb = g_ref[0, :, cols].astype(jnp.float32)
                    ub = u_ref[0, :, cols].astype(jnp.float32)
                    dzg = (dhb * ub * act_bwd(gb, "silu")
                           ).astype(dh_ref.dtype)
                    dzu = (dhb * act_fwd(gb, "silu")).astype(dh_ref.dtype)
                    part = (_rev_dot(dzg, wgbuf[p % 2, j])
                            + _rev_dot(dzu, wibuf[p % 2, j]))
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
        gated_dx_kernel,
        name=_kernel_name("junction_gated_dx", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, M // bm, nib),
            in_specs=_counted_specs([row, row, row, hbm, hbm], counts, rows),
            out_specs=_counted_specs(out_specs, counts, rows)[0],
            scratch_shapes=[pltpu.VMEM((2, 2, bs, bs), wg.dtype),
                            pltpu.VMEM((2, 2, bs, bs), wi.dtype),
                            pltpu.SemaphoreType.DMA((2, 2, 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((E, M, nib * bs), dh.dtype),
        interpret=interpret,
    )(*prefetch, dh, g, u, wg_flat, wi_flat)


# ------------------------------------------------------------------ dw (+db)
def dw(x, dy, idx, res, *, act: str = "none", with_bias: bool = True,
       bm: int | None = None, interpret: bool = False, counts=None):
    """(dw [E, nob, kb, bs, bs] fp32, db [E, nob*bs] fp32 or None) — grid
    (E, nob, M/bm) with the M reduction innermost into fp32 VMEM scratch,
    flushed once per (unit, output block).  The kb gathered input blocks
    arrive through scalar-prefetch BlockSpec index_maps — the interleaver
    as a DMA descriptor — and, for biased layers, db accumulates from the
    same fused dz prologue (with_bias=False skips it entirely).
    ``counts``: live rows per unit, as in ``fwd`` (dead row tiles add
    nothing)."""
    E, M, _ = x.shape
    nob, kb = idx.shape
    bs = dy.shape[2] // nob
    has_res = act != "none"
    if bm is None:
        bm = bwd_bm(M, kb + 3, bs, x.dtype.itemsize)
    assert M % bm == 0
    nm = M // bm

    def dw_kernel(idx_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        n_in = (2 if has_res else 1) + kb
        dy_ref = refs[0]
        res_ref = refs[1] if has_res else None
        x_refs = refs[n_in - kb:n_in]
        if with_bias:
            dw_ref, db_ref, accw_ref, accb_ref = refs[n_in:]
        else:
            dw_ref, accw_ref = refs[n_in:]
        m = pl.program_id(2)

        @pl.when(m == 0)
        def _zero():
            accw_ref[...] = jnp.zeros((kb, bs, bs), jnp.float32)
            if with_bias:
                accb_ref[...] = jnp.zeros((1, bs), jnp.float32)

        def _accumulate():
            if has_res:
                grad = act_bwd(res_ref[0].astype(jnp.float32), act)
                dzf = dy_ref[0].astype(jnp.float32) * grad
                dz = dzf.astype(dy_ref.dtype)
            else:
                dzf = None
                dz = dy_ref[0]
            for k in range(kb):
                accw_ref[k] = accw_ref[k] + jnp.dot(
                    x_refs[k][0].T, dz, preferred_element_type=jnp.float32)
            if with_bias:
                s = dzf if dzf is not None else dy_ref[0].astype(jnp.float32)
                accb_ref[...] = accb_ref[...] + jnp.sum(s, axis=0,
                                                        keepdims=True)

        _run_live(_accumulate, cnt_ref, bm, 2)

        @pl.when(m == nm - 1)
        def _flush():
            dw_ref[...] = accw_ref[...][None, None]
            if with_bias:
                db_ref[0] = accb_ref[...]

    in_specs = [pl.BlockSpec((1, bm, bs), lambda e, o, m, idx: (e, m, o))]
    inputs = [dy]
    if has_res:
        in_specs.append(pl.BlockSpec((1, bm, bs),
                                     lambda e, o, m, idx: (e, m, o)))
        inputs.append(res)
    for k in range(kb):
        in_specs.append(pl.BlockSpec(
            (1, bm, bs), lambda e, o, m, idx, k=k: (e, m, idx[o, k])))
        inputs.append(x)

    out_specs = [pl.BlockSpec((1, 1, kb, bs, bs),
                              lambda e, o, m, idx: (e, o, 0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((E, nob, kb, bs, bs), jnp.float32)]
    scratch = [pltpu.VMEM((kb, bs, bs), jnp.float32)]
    if with_bias:
        out_specs.append(pl.BlockSpec((1, 1, bs), lambda e, o, m, idx: (e, 0, o)))
        out_shape.append(jax.ShapeDtypeStruct((E, 1, nob * bs), jnp.float32))
        scratch.append(pltpu.VMEM((1, bs), jnp.float32))

    prefetch = (idx,) if counts is None else (idx, counts)
    rows = lambda im: _rows_inner(im, bm)
    outs = pl.pallas_call(
        dw_kernel,
        name=_kernel_name("junction_dw", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, nob, nm),
            in_specs=_counted_specs(in_specs, counts, rows),
            out_specs=_counted_specs(out_specs, counts, rows),
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
    )(*prefetch, *inputs)
    if with_bias:
        return outs[0], outs[1].reshape(E, -1)
    return outs[0], None


def _gated_accumulate(dh_ref, g_ref, u_ref, x_refs, accg_ref, accu_ref,
                      kb: int):
    """One row tile of the gated junction's two weight-gradient
    reductions: both branch grads recomputed from the (g, u) residuals,
    then every fan-in slot's ``x^T dz`` added into its VMEM scratch."""
    dhb = dh_ref[0].astype(jnp.float32)
    gb = g_ref[0].astype(jnp.float32)
    ub = u_ref[0].astype(jnp.float32)
    dzg = (dhb * ub * act_bwd(gb, "silu")).astype(dh_ref.dtype)
    dzu = (dhb * act_fwd(gb, "silu")).astype(dh_ref.dtype)
    for k in range(kb):
        xT = x_refs[k][0].T
        accg_ref[k] = accg_ref[k] + jnp.dot(
            xT, dzg, preferred_element_type=jnp.float32)
        accu_ref[k] = accu_ref[k] + jnp.dot(
            xT, dzu, preferred_element_type=jnp.float32)


def gated_dw(x, dh, idx, g, u, *, bm: int | None = None,
             interpret: bool = False, counts=None):
    """(dwg, dwi) [E, nob, kb, bs, bs] fp32 for the fused gated FFN — the
    two branch grads are recomputed in the prologue from the (g, u)
    residuals and both M reductions accumulate innermost into separate
    VMEM scratch buffers, flushed once per (unit, output block).
    ``counts``: live rows per unit, as in ``dw``."""
    E, M, _ = x.shape
    nob, kb = idx.shape
    bs = dh.shape[2] // nob
    if bm is None:
        bm = bwd_bm(M, kb + 5, bs, x.dtype.itemsize)
    assert M % bm == 0
    nm = M // bm

    def gated_dw_kernel(idx_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        dh_ref, g_ref, u_ref = refs[:3]
        x_refs = refs[3:3 + kb]
        dwg_ref, dwi_ref, accg_ref, accu_ref = refs[3 + kb:]
        m = pl.program_id(2)

        @pl.when(m == 0)
        def _zero():
            accg_ref[...] = jnp.zeros((kb, bs, bs), jnp.float32)
            accu_ref[...] = jnp.zeros((kb, bs, bs), jnp.float32)

        def _accumulate():
            _gated_accumulate(dh_ref, g_ref, u_ref, x_refs, accg_ref,
                              accu_ref, kb)

        _run_live(_accumulate, cnt_ref, bm, 2)

        @pl.when(m == nm - 1)
        def _flush():
            dwg_ref[...] = accg_ref[...][None, None]
            dwi_ref[...] = accu_ref[...][None, None]

    row = pl.BlockSpec((1, bm, bs), lambda e, o, m, idx: (e, m, o))
    in_specs = [row, row, row]
    inputs = [dh, g, u]
    for k in range(kb):
        in_specs.append(pl.BlockSpec(
            (1, bm, bs), lambda e, o, m, idx, k=k: (e, m, idx[o, k])))
        inputs.append(x)

    wout = pl.BlockSpec((1, 1, kb, bs, bs), lambda e, o, m, idx: (e, o, 0, 0, 0))
    prefetch = (idx,) if counts is None else (idx, counts)
    rows = lambda im: _rows_inner(im, bm)
    outs = pl.pallas_call(
        gated_dw_kernel,
        name=_kernel_name("junction_gated_dw", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(E, nob, nm),
            in_specs=_counted_specs(in_specs, counts, rows),
            out_specs=_counted_specs([wout, wout], counts, rows),
            scratch_shapes=[pltpu.VMEM((kb, bs, bs), jnp.float32),
                            pltpu.VMEM((kb, bs, bs), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((E, nob, kb, bs, bs), jnp.float32),
                   jax.ShapeDtypeStruct((E, nob, kb, bs, bs), jnp.float32)],
        interpret=interpret,
    )(*prefetch, *inputs)
    return outs[0], outs[1]


# --------------------------------------------------- fused BP+UP (update_dw)
N_SCALAR_PREFETCH_UPDATE = 2    # (idx, hyp) — alias indices count these

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


def update_dw(x, dy, idx, res, w, b, mom, mom_b, hyp, *, vel=None,
              vel_b=None, act: str = "none", with_bias: bool = True,
              bm: int | None = None, with_health: bool = False,
              interpret: bool = False, counts=None):
    """The fused UP stage: the ``dw`` gradient reduction with the
    optimizer update applied in the flush epilogue — returns
    ``(new_w, new_b, new_mom, new_mom_b, new_vel, new_vel_b, health)``
    (None where the operand is absent) instead of ``(dw, db)``, with
    every parameter operand aliased to its output
    (``input_output_aliases``), so the weight gradient never leaves VMEM
    scratch and the parameters are rewritten in place.

    hyp is the scalar-prefetched per-unit ``[E, HYP_K]`` table of the
    module docstring's column registry (any shape ``normalize_hyp``
    accepts) — the epilogue reads row ``e = program_id(0)``, so each
    junction unit updates under its own hyperparameters.  The
    accumulator slots select the optimizer statically: mom/mom_b alone
    → SGD(+momentum), plus vel/vel_b → Adam (m, v); all slots fp32.
    Same grid, BlockSpecs and default row tile as ``dw``, so the fp32
    accumulation order matches the two-pass path exactly (parity to
    fp32 round-off).

    ``with_health=True`` adds a tiny non-aliased ``[E]`` int32 output
    riding the same flush: each (e, ob) epilogue OR-reduces
    ``isfinite`` over the accumulator tiles it just wrote (both m and v
    for Adam, and the bias update for biased layers) and accumulates one
    count into unit e's slot — the in-kernel divergence detector (one
    VMEM compare per tile; the gradient still never materializes in
    HBM).  health[e] > 0 means unit e wrote at least one non-finite
    parameter tile this step.

    ``counts``: live rows per unit, as in ``dw``; every (unit, output
    block) is still updated, a unit with no live row from its moments."""
    E, M, _ = x.shape
    nob, kb = idx.shape
    bs = dy.shape[2] // nob
    has_res = act != "none"
    has_mom = mom is not None
    has_vel = vel is not None
    assert not has_vel or has_mom, "Adam (vel) requires the mom slot too"
    assert not (has_vel and with_bias) or vel_b is not None
    hyp = normalize_hyp(hyp, E)
    if bm is None:
        bm = bwd_bm(M, kb + 3, bs, x.dtype.itemsize)
    assert M % bm == 0
    nm = M // bm

    def fused_update_dw(idx_ref, hyp_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        n_lead = 2 if has_res else 1
        dy_ref = refs[0]
        res_ref = refs[1] if has_res else None
        x_refs = refs[n_lead:n_lead + kb]
        pos = n_lead + kb
        w_ref = refs[pos]
        pos += 1
        mom_ref = refs[pos] if has_mom else None
        pos += int(has_mom)
        vel_ref = refs[pos] if has_vel else None
        pos += int(has_vel)
        b_ref = refs[pos] if with_bias else None
        pos += int(with_bias)
        mom_b_ref = refs[pos] if (has_mom and with_bias) else None
        pos += int(has_mom and with_bias)
        vel_b_ref = refs[pos] if (has_vel and with_bias) else None
        pos += int(has_vel and with_bias)
        outs = list(refs[pos:])
        new_w_ref = outs.pop(0)
        new_mom_ref = outs.pop(0) if has_mom else None
        new_vel_ref = outs.pop(0) if has_vel else None
        new_b_ref = outs.pop(0) if with_bias else None
        new_mom_b_ref = outs.pop(0) if (has_mom and with_bias) else None
        new_vel_b_ref = outs.pop(0) if (has_vel and with_bias) else None
        health_ref = outs.pop(0) if with_health else None
        if with_bias:
            accw_ref, accb_ref = outs
        else:
            (accw_ref,) = outs
        e = pl.program_id(0)
        o = pl.program_id(1)
        m = pl.program_id(2)

        @pl.when(m == 0)
        def _zero():
            accw_ref[...] = jnp.zeros((kb, bs, bs), jnp.float32)
            if with_bias:
                accb_ref[...] = jnp.zeros((1, bs), jnp.float32)

        if with_health:
            # health slot e is revisited across every (o, m) step: init once
            @pl.when(jnp.logical_and(o == 0, m == 0))
            def _zero_health():
                health_ref[...] = jnp.zeros(health_ref.shape, jnp.int32)

        def _accumulate():
            if has_res:
                grad = act_bwd(res_ref[0].astype(jnp.float32), act)
                dzf = dy_ref[0].astype(jnp.float32) * grad
                dz = dzf.astype(dy_ref.dtype)
            else:
                dzf = None
                dz = dy_ref[0]
            for k in range(kb):
                accw_ref[k] = accw_ref[k] + jnp.dot(
                    x_refs[k][0].T, dz, preferred_element_type=jnp.float32)
            if with_bias:
                s = dzf if dzf is not None else dy_ref[0].astype(jnp.float32)
                accb_ref[...] = accb_ref[...] + jnp.sum(s, axis=0,
                                                        keepdims=True)

        _run_live(_accumulate, cnt_ref, bm, 2)

        @pl.when(m == nm - 1)
        def _apply():
            def h(col):
                return hyp_ref[e, col]

            new_w32, nmv, nvv, ok = _epilogue_step(
                h, accw_ref[...], w_ref[0, 0].astype(jnp.float32),
                mom_ref[0, 0] if has_mom else None,
                vel_ref[0, 0] if has_vel else None, with_health)
            if has_mom:
                new_mom_ref[0, 0] = nmv
            if has_vel:
                new_vel_ref[0, 0] = nvv
            new_w_ref[0, 0] = new_w32.astype(new_w_ref.dtype)
            if with_bias:
                new_b32, nmb, nvb, okb = _epilogue_step(
                    h, accb_ref[...], b_ref[0].astype(jnp.float32),
                    mom_b_ref[0] if has_mom else None,
                    vel_b_ref[0] if has_vel else None, with_health)
                if has_mom:
                    new_mom_b_ref[0] = nmb
                if has_vel:
                    new_vel_b_ref[0] = nvb
                new_b_ref[0] = new_b32.astype(new_b_ref.dtype)
                if with_health:
                    ok = jnp.logical_and(ok, okb)
            if with_health:
                _flag_unhealthy(health_ref, ok)

    in_specs = [pl.BlockSpec((1, bm, bs), lambda e, o, m, *_: (e, m, o))]
    inputs = [dy]
    if has_res:
        in_specs.append(pl.BlockSpec((1, bm, bs),
                                     lambda e, o, m, *_: (e, m, o)))
        inputs.append(res)
    for k in range(kb):
        in_specs.append(pl.BlockSpec(
            (1, bm, bs), lambda e, o, m, idx, hyp, k=k: (e, m, idx[o, k])))
        inputs.append(x)

    wspec = pl.BlockSpec((1, 1, kb, bs, bs), lambda e, o, m, *_: (e, o, 0, 0, 0))
    # per-unit bias operands ride as [E, 1, N]: the block's last two dims
    # (1, bs) then equal / tile the array's for every E
    bspec = pl.BlockSpec((1, 1, bs), lambda e, o, m, *_: (e, 0, o))
    aliases: dict[int, int] = {}
    out_specs, out_shape = [], []

    n_prefetch = N_SCALAR_PREFETCH_UPDATE + (counts is not None)

    def alias_io(arr, spec):
        """Parameter operand riding in AND out through the same BlockSpec —
        the in-place update contract."""
        aliases[n_prefetch + len(inputs)] = len(out_shape)
        in_specs.append(spec)
        inputs.append(arr)
        out_specs.append(spec)
        out_shape.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))

    alias_io(w, wspec)
    if has_mom:
        alias_io(mom, wspec)
    if has_vel:
        alias_io(vel, wspec)
    if with_bias:
        alias_io(b.reshape(E, 1, -1), bspec)
        if has_mom:
            alias_io(mom_b.reshape(E, 1, -1), bspec)
        if has_vel:
            alias_io(vel_b.reshape(E, 1, -1), bspec)
    if with_health:
        out_specs.append(HEALTH_SPEC)
        out_shape.append(_health_shape(E))

    scratch = [pltpu.VMEM((kb, bs, bs), jnp.float32)]
    if with_bias:
        scratch.append(pltpu.VMEM((1, bs), jnp.float32))

    prefetch = (idx, hyp) if counts is None else (idx, hyp, counts)
    rows = lambda im: _rows_inner(im, bm)
    outs = pl.pallas_call(
        fused_update_dw,
        name=_kernel_name("junction_update_dw", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(E, nob, nm),
            in_specs=_counted_specs(in_specs, counts, rows),
            out_specs=_counted_specs(out_specs, counts, rows),
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*prefetch, *inputs)
    outs = list(outs)
    new_w = outs.pop(0)
    new_mom = outs.pop(0) if has_mom else None
    new_vel = outs.pop(0) if has_vel else None
    unit_rows = lambda a: a.reshape(E, -1)
    new_b = unit_rows(outs.pop(0)) if with_bias else None
    new_mom_b = unit_rows(outs.pop(0)) if (has_mom and with_bias) else None
    new_vel_b = unit_rows(outs.pop(0)) if (has_vel and with_bias) else None
    health = outs.pop(0)[:, 0, 0] if with_health else None
    return new_w, new_b, new_mom, new_mom_b, new_vel, new_vel_b, health


def update_gated_dw(x, dh, idx, g, u, wg, wi, mg, mi, hyp, *, vg=None,
                    vi=None, bm: int | None = None,
                    with_health: bool = False, interpret: bool = False,
                    counts=None):
    """Fused BP+UP for the gated junction: both branch gradients reduce
    into VMEM scratch exactly as in ``gated_dw`` and the flush epilogue
    applies the optimizer update to BOTH weight streams in place —
    returns ``(new_wg, new_wi, new_mg, new_mi, new_vg, new_vi, health)``
    (absent slots None), all parameter outputs aliased to their inputs.
    hyp is the per-unit ``[E, HYP_K]`` table (any shape ``normalize_hyp``
    accepts), row ``e`` read in the epilogue; the slots select the
    optimizer statically — mg/mi alone → SGD(+momentum), plus vg/vi →
    Adam.  ``with_health=True`` appends the non-aliased ``[E]``
    int32 divergence detector (see ``update_dw``): the epilogue checks
    BOTH branch update tiles for non-finites.  ``counts``: live rows per
    unit, as in ``update_dw``."""
    E, M, _ = x.shape
    nob, kb = idx.shape
    bs = dh.shape[2] // nob
    has_mom = mg is not None
    has_vel = vg is not None
    assert not has_vel or (has_mom and vi is not None), \
        "Adam (vg/vi) requires the mg/mi slots too"
    hyp = normalize_hyp(hyp, E)
    if bm is None:
        bm = bwd_bm(M, kb + 5, bs, x.dtype.itemsize)
    assert M % bm == 0
    nm = M // bm

    def fused_update_gated_dw(idx_ref, hyp_ref, *refs):
        cnt_ref = None
        if counts is not None:
            cnt_ref, *refs = refs
        dh_ref, g_ref, u_ref = refs[:3]
        x_refs = refs[3:3 + kb]
        pos = 3 + kb
        wg_ref, wi_ref = refs[pos], refs[pos + 1]
        pos += 2
        if has_mom:
            mg_ref, mi_ref = refs[pos], refs[pos + 1]
            pos += 2
        if has_vel:
            vg_ref, vi_ref = refs[pos], refs[pos + 1]
            pos += 2
        outs = list(refs[pos:])
        new_wg_ref = outs.pop(0)
        new_wi_ref = outs.pop(0)
        if has_mom:
            new_mg_ref = outs.pop(0)
            new_mi_ref = outs.pop(0)
        if has_vel:
            new_vg_ref = outs.pop(0)
            new_vi_ref = outs.pop(0)
        health_ref = outs.pop(0) if with_health else None
        accg_ref, accu_ref = outs
        e = pl.program_id(0)
        o = pl.program_id(1)
        m = pl.program_id(2)

        @pl.when(m == 0)
        def _zero():
            accg_ref[...] = jnp.zeros((kb, bs, bs), jnp.float32)
            accu_ref[...] = jnp.zeros((kb, bs, bs), jnp.float32)

        if with_health:
            @pl.when(jnp.logical_and(o == 0, m == 0))
            def _zero_health():
                health_ref[...] = jnp.zeros(health_ref.shape, jnp.int32)

        def _accumulate():
            _gated_accumulate(dh_ref, g_ref, u_ref, x_refs, accg_ref,
                              accu_ref, kb)

        _run_live(_accumulate, cnt_ref, bm, 2)

        @pl.when(m == nm - 1)
        def _apply():
            def h(col):
                return hyp_ref[e, col]

            new_g32, nmg, nvg, okg = _epilogue_step(
                h, accg_ref[...], wg_ref[0, 0].astype(jnp.float32),
                mg_ref[0, 0] if has_mom else None,
                vg_ref[0, 0] if has_vel else None, with_health)
            new_i32, nmi, nvi, oki = _epilogue_step(
                h, accu_ref[...], wi_ref[0, 0].astype(jnp.float32),
                mi_ref[0, 0] if has_mom else None,
                vi_ref[0, 0] if has_vel else None, with_health)
            if has_mom:
                new_mg_ref[0, 0] = nmg
                new_mi_ref[0, 0] = nmi
            if has_vel:
                new_vg_ref[0, 0] = nvg
                new_vi_ref[0, 0] = nvi
            new_wg_ref[0, 0] = new_g32.astype(new_wg_ref.dtype)
            new_wi_ref[0, 0] = new_i32.astype(new_wi_ref.dtype)
            if with_health:
                _flag_unhealthy(health_ref, jnp.logical_and(okg, oki))

    row = pl.BlockSpec((1, bm, bs), lambda e, o, m, *_: (e, m, o))
    in_specs = [row, row, row]
    inputs = [dh, g, u]
    for k in range(kb):
        in_specs.append(pl.BlockSpec(
            (1, bm, bs), lambda e, o, m, idx, hyp, k=k: (e, m, idx[o, k])))
        inputs.append(x)

    wspec = pl.BlockSpec((1, 1, kb, bs, bs), lambda e, o, m, *_: (e, o, 0, 0, 0))
    aliases: dict[int, int] = {}
    out_specs, out_shape = [], []
    n_prefetch = N_SCALAR_PREFETCH_UPDATE + (counts is not None)

    def alias_io(arr):
        aliases[n_prefetch + len(inputs)] = len(out_shape)
        in_specs.append(wspec)
        inputs.append(arr)
        out_specs.append(wspec)
        out_shape.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))

    alias_io(wg)
    alias_io(wi)
    if has_mom:
        alias_io(mg)
        alias_io(mi)
    if has_vel:
        alias_io(vg)
        alias_io(vi)
    if with_health:
        out_specs.append(HEALTH_SPEC)
        out_shape.append(_health_shape(E))

    prefetch = (idx, hyp) if counts is None else (idx, hyp, counts)
    rows = lambda im: _rows_inner(im, bm)
    outs = pl.pallas_call(
        fused_update_gated_dw,
        name=_kernel_name("junction_update_gated_dw", counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(E, nob, nm),
            in_specs=_counted_specs(in_specs, counts, rows),
            out_specs=_counted_specs(out_specs, counts, rows),
            scratch_shapes=[pltpu.VMEM((kb, bs, bs), jnp.float32),
                            pltpu.VMEM((kb, bs, bs), jnp.float32)],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*prefetch, *inputs)
    outs = list(outs)
    new_wg = outs.pop(0)
    new_wi = outs.pop(0)
    new_mg = outs.pop(0) if has_mom else None
    new_mi = outs.pop(0) if has_mom else None
    new_vg = outs.pop(0) if has_vel else None
    new_vi = outs.pop(0) if has_vel else None
    health = outs.pop(0)[:, 0, 0] if with_health else None
    return new_wg, new_wi, new_mg, new_mi, new_vg, new_vi, health
