"""jit'd public wrappers around the Pallas kernels.

``junction_matmul`` is the ONE entry point for every pre-defined-sparse
junction — the paper's reconfigurable edge datapath as a single
custom_vjp.  A ``KernelSpec`` (expert count E, gate flag, activation,
tiles) selects the configuration; the kernels themselves are E-generic
(kernels/block_sparse_matmul.py), so:

* a single dense-model junction (``core/sparse_linear.apply``) is the
  ``E=1`` case — 4-D weights are squeezed in, the result squeezed out;
* MoE expert FFNs (``models/moe.moe_apply``) pass 5-D per-expert weights
  ``[E, nob, kb, bs, bs]`` sharing one block pattern;
* ``wi=`` switches on the fused SwiGLU gate ``silu(x@w) * (x@wi)`` with
  both branch grads recomputed from the saved (g, u) residuals.

The backward runs the full paper pipeline in Pallas: BP (eq. (2))
through ``dx`` — whose reverse weight bundles are DMA'd HBM→VMEM inside
the kernel (double-buffered, offsets from the scalar-prefetched reverse
pattern), NOT pre-gathered in XLA — and UP (gradient of eq. (3)) through
``dw``, with the activation gradient recomputed in the kernel prologues
from the saved residual so the elementwise grad tensor never round-trips
HBM.

``junction_train_update`` is the fused BP+UP twin: same forward, but the
backward consumes the weight gradient *inside* the update kernels —
``w -= lr * (momentum * m + dw)`` applied in the kernel epilogue with the
updated params/momenta returned as the weight operands' cotangents
through ``input_output_aliasing`` — so ``dw`` never round-trips HBM (the
paper's concurrent BP/UP pipeline; Dey et al. 2017's interleaved FF/BP/UP
edge processor).

Kernels execute in interpret mode off-TPU (the container is CPU-only);
on TPU ``interpret=False`` (the default auto-detects the backend).

``resolve_engine`` maps the config-level ``engine`` switch
("pallas" | "jnp" | "auto") to a concrete path: auto picks the Pallas
engine on TPU backends and the jnp gather+einsum fallback elsewhere
(interpret-mode Pallas is an emulator — correct, but only suitable for
tests; CPU *tests* opt in with engine="pallas" explicitly).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import block_sparse_matmul as bsm
from repro.kernels import fxp_qmatmul as fxpk
from repro.kernels import sigmoid_lut as slut


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_engine(engine: str) -> str:
    """'auto' -> 'pallas' on TPU backends, 'jnp' elsewhere."""
    if engine in ("pallas", "jnp"):
        return engine
    if engine == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    raise ValueError(f"unknown engine {engine!r} (pallas | jnp | auto)")


# --------------------------------------------------------- junction matmul
class KernelSpec(NamedTuple):
    """Static (hashable) configuration of the unified junction custom_vjp:
    the paper's 'reconfigure the one datapath per junction' knob set."""
    E: int              # junction units sharing the pattern (1 = single)
    gated: bool         # fused SwiGLU gate (two weight operands, silu fixed)
    act: str            # fused epilogue activation ("none" when gated)
    bm: int             # row tile
    bn: int             # output-bundle tile
    has_bias: bool
    interpret: bool
    with_health: bool = False   # fused update emits the [E] divergence flags
    # "none" | "int8" | "fxp" — the quantized-inference configurations
    # (core/quantize.py).  Quantized specs are forward-only: they bypass
    # the custom_vjp entirely and junction_train_update refuses them.
    quant: str = "none"


def _kernel_scope(name: str, spec: KernelSpec):
    """Profiler attribution for the junction entry points: a
    ``jax.named_scope`` keyed off the KernelSpec knobs (E / gated / act /
    quant), so a ``jax.profiler`` trace (``--profile`` on the launchers)
    shows e.g. ``junction_train_update_E16_gated`` instead of an
    anonymous pallas_call.  Pure metadata on the jaxpr scope stack — adds
    no ops and changes no jaxpr equations (regression-tested in
    tests/test_obs.py)."""
    tag = f"{name}_E{spec.E}"
    if spec.gated:
        tag += "_gated"
    elif spec.act != "none":
        tag += f"_{spec.act}"
    if spec.quant != "none":
        tag += f"_{spec.quant}"
    return jax.named_scope(tag)


def _bwd_tile(spec, counts) -> dict:
    """Counted calls run every kernel on the forward's row tile, so the
    live tiles (and the rows they cover) are the same in each."""
    return {} if counts is None else {"bm": spec.bm}


def _fwd_call(spec, x, ws, b, idx, save: bool, counts=None):
    """(y, res) through the forward kernel; res is the backward's
    residuals — (g, u) for the gate, (pre-activation,) or (y,) for plain
    activations, () for none — emitted only when ``save``."""
    keep = save and (spec.gated or spec.act in bsm.ACT_NEEDS_PRE)
    y, saved = bsm.junction_fwd(x, ws, idx, None if spec.gated else b,
                                act=spec.act, bm=spec.bm, bn=spec.bn,
                                save=keep, interpret=spec.interpret,
                                counts=counts)
    if not save:
        return y, None
    return y, saved or ((y,) if spec.act != "none" else ())


def _dx_call(spec, ws, res, dy, rev_ob, rev_t, rev_cnt, counts=None):
    """BP through the reverse pattern — the reverse weight bundles are
    DMA'd HBM→VMEM inside the kernel from the forward-layout weights (no
    XLA w[rev_ob, rev_t] pre-gather)."""
    return bsm.junction_dx(dy, ws, rev_ob, rev_t, rev_cnt, res, act=spec.act,
                           interpret=spec.interpret, counts=counts,
                           **_bwd_tile(spec, counts))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _junction_core(spec, x, ws, b, idx, rev_ob, rev_t, rev_cnt, counts):
    """x [E, M, nib*bs], ws tuple of 1 (plain) or 2 (gated) weight tensors
    [E, nob, kb, bs, bs], b [E, nob*bs] -> y [E, M, nob*bs]; counts: live
    rows per unit [E] int32, or None (every row)."""
    y, _ = _fwd_call(spec, x, ws, b, idx, save=False, counts=counts)
    return y


def _junction_fwd(spec, x, ws, b, idx, rev_ob, rev_t, rev_cnt, counts):
    y, res = _fwd_call(spec, x, ws, b, idx, save=True, counts=counts)
    return y, (x, ws, res, idx, rev_ob, rev_t, rev_cnt, counts)


def _junction_bwd(spec, saved, dy):
    x, ws, res, idx, rev_ob, rev_t, rev_cnt, counts = saved
    dxv = _dx_call(spec, ws, res, dy, rev_ob, rev_t, rev_cnt, counts)
    dws, dbv = bsm.junction_dw(x, dy, idx, res, act=spec.act,
                               with_bias=spec.has_bias,
                               interpret=spec.interpret, counts=counts,
                               **_bwd_tile(spec, counts))
    if dbv is None:  # bias-free layer: the zero-bias operand gets zeros
        dbv = jnp.zeros((dy.shape[0], dy.shape[2]), jnp.float32)
    dws = tuple(d.astype(w.dtype) for d, w in zip(dws, ws))
    return dxv, dws, dbv, None, None, None, None, None


_junction_core.defvjp(_junction_fwd, _junction_bwd)


# ------------------------------------------------- fused BP+UP custom_vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _junction_update_core(spec, x, ws, b, moms, mom_b, vels, vel_b, hyp,
                          health, idx, rev_ob, rev_t, rev_cnt, counts):
    """Forward identical to _junction_core; the vjp's cotangents for the
    parameter operands are the optimizer-UPDATED values computed by the
    fused update_dw kernels (kernels/block_sparse_matmul.py) — the
    paper's concurrent BP+UP pipeline.  moms/vels are accumulator-slot
    tuples mirroring ws (both empty = plain SGD, moms alone =
    SGD+momentum, both = Adam m/v — the kernels' static slot switch),
    mom_b/vel_b the matching 0/1-tuples for the bias, hyp the per-unit
    [E, HYP_K] f32 table of the kernel module's column registry.  The
    weight gradient never materializes in HBM: it lives in VMEM scratch
    and is consumed by the in-kernel update, whose outputs alias the
    parameter inputs.

    ``health`` is a dummy f32 [E] operand riding the same cotangent
    channel: when ``spec.with_health`` the update kernels' non-aliased
    [E] int32 divergence flags come back as its cotangent (count of
    non-finite update tiles per unit), so the in-kernel detector
    surfaces through an ordinary jax.grad without materializing any
    gradient — the forward ignores the operand entirely.

    ``counts`` (live rows per unit, or None) skips dead row tiles in
    every kernel; the update epilogue still runs for every unit."""
    y, _ = _fwd_call(spec, x, ws, b, idx, save=False, counts=counts)
    return y


def _junction_update_fwd(spec, x, ws, b, moms, mom_b, vels, vel_b, hyp,
                         health, idx, rev_ob, rev_t, rev_cnt, counts):
    y, res = _fwd_call(spec, x, ws, b, idx, save=True, counts=counts)
    return y, (x, ws, b, res, moms, mom_b, vels, vel_b, hyp, idx, rev_ob,
               rev_t, rev_cnt, counts)


def _junction_update_bwd(spec, saved, dy):
    (x, ws, b, res, moms, mom_b, vels, vel_b, hyp, idx, rev_ob, rev_t,
     rev_cnt, counts) = saved
    dxv = _dx_call(spec, ws, res, dy, rev_ob, rev_t, rev_cnt, counts)
    kw = dict(with_health=spec.with_health, interpret=spec.interpret,
              counts=counts, **_bwd_tile(spec, counts))
    slot = lambda t, i=0: t[i] if t else None
    # through the per-form names, which a test may replace
    if spec.gated:
        *new, flags = bsm.update_gated_dw(
            x, dy, idx, *res, *ws, slot(moms), slot(moms, 1), hyp,
            vg=slot(vels), vi=slot(vels, 1), **kw)
        new_ws = tuple(new[:2])
        new_moms = tuple(new[2:4]) if moms else ()
        new_vels = tuple(new[4:]) if vels else ()
        new_b = jnp.zeros_like(b)    # gated junctions carry no bias
        new_mom_b = new_vel_b = ()
    else:
        nw, nb, nm, nmb, nv, nvb, flags = bsm.update_dw(
            x, dy, idx, slot(res), ws[0], b if spec.has_bias else None,
            slot(moms), slot(mom_b), hyp, vel=slot(vels),
            vel_b=slot(vel_b), act=spec.act, with_bias=spec.has_bias, **kw)
        new_ws = (nw,)
        new_moms = (nm,) if moms else ()
        new_vels = (nv,) if vels else ()
        new_b = nb if spec.has_bias else jnp.zeros_like(b)
        new_mom_b = (nmb,) if mom_b else ()
        new_vel_b = (nvb,) if vel_b else ()
    d_health = (flags.astype(jnp.float32)
                if spec.with_health else jnp.zeros((spec.E,), jnp.float32))
    return (dxv, new_ws, new_b, new_moms, new_mom_b, new_vels, new_vel_b,
            jnp.zeros_like(hyp), d_health, None, None, None, None, None)


_junction_update_core.defvjp(_junction_update_fwd, _junction_update_bwd)


def junction_matmul(x, w, idx, rev_ob, rev_t, rev_cnt, *, wi=None, bias=None,
                    act: str = "none", interpret: bool | None = None,
                    bm: int | None = None, bn: int | None = None,
                    w_scale=None, wi_scale=None, x_scale=None,
                    qfmt=None, qlut=None, counts=None):
    """The unified junction: y = act(x @ W_sparse + bias) through the
    pre-defined block pattern, every configuration through ONE custom_vjp.

    * ``w.ndim == 4`` (``[nob, kb, bs, bs]``): single junction.  x may
      carry any leading dims ``[..., n_in]``; runs as the kernels' E=1
      case and is squeezed back to ``[..., n_out]``.
    * ``w.ndim == 5`` (``[E, nob, kb, bs, bs]``): E junction units
      sharing the pattern (MoE experts).  x ``[E, M, n_in]``, bias
      ``[E, n_out]`` -> y ``[E, M, n_out]``.
    * ``wi=`` (same shape as w): fused SwiGLU gate
      ``silu(x @ w) * (x @ wi)`` — one forward pass, two-branch fused
      backward; ``act``/``bias`` must stay at their defaults.
    * quantized inference (``core/quantize.py`` leaves): ``w_scale``
      (``[nob, kb]`` / ``[E, nob, kb]`` — with ``wi_scale`` for the
      gate) selects the int8 path with optional calibrated ``x_scale``;
      ``qfmt`` + ``qlut`` select full fixed-point (plain junctions
      only, LUT replaces ``act``).  These specs are FORWARD-ONLY — no
      custom_vjp; differentiate the fp junction instead.
    * ``counts`` (int32 ``[E]``, 5-D weights only): unit e holds
      ``counts[e]`` live rows at the top of its ``M``; row tiles past it
      are skipped in every kernel, forward and backward, and rows past
      it come back unwritten.  The kernels then carry the
      ``expert_junction_`` names.
    """
    interpret = _auto_interpret() if interpret is None else interpret
    gated = wi is not None
    if gated and (bias is not None or act != "none"):
        raise ValueError("gated junction fixes act=silu-gate and takes no bias")
    if qfmt is not None or w_scale is not None:
        return _junction_quant(x, w, idx, wi=wi, bias=bias, act=act,
                               interpret=interpret, bm=bm, bn=bn,
                               w_scale=w_scale, wi_scale=wi_scale,
                               x_scale=x_scale, qfmt=qfmt, qlut=qlut)
    if jnp.issubdtype(w.dtype, jnp.integer):
        raise ValueError(
            "integer-code weights need their quantization leaves "
            "(w_scale for int8, qfmt+qlut for fixed point) — refusing to "
            "cast codes to floats silently")
    single, lead, x3, w5, wi5, b2, E, M, nob, bs, bm, bn = _prep_junction(
        x, w, wi, bias, bm, bn, gated)
    _check_counts(counts, single, E)
    b = (jnp.zeros((E, nob * bs), x.dtype) if b2 is None
         else b2.astype(x.dtype))
    ws = ((w5.astype(x.dtype), wi5.astype(x.dtype)) if gated
          else (w5.astype(x.dtype),))
    spec = KernelSpec(E=E, gated=gated, act=act, bm=bm, bn=bn,
                      has_bias=bias is not None, interpret=interpret)
    with _kernel_scope("junction_matmul", spec):
        y = _junction_core(spec, x3, ws, b, idx, rev_ob, rev_t, rev_cnt,
                           counts)
    y = y[:, :M]
    return y.reshape(*lead, nob * bs) if single else y


def _junction_quant(x, w, idx, *, wi, bias, act, interpret, bm, bn,
                    w_scale, wi_scale, x_scale, qfmt, qlut):
    """Forward-only dispatch of the quantized KernelSpec configurations:
    same shape lifting / tile selection / row padding as the fp path,
    scales and codes lifted alongside, then a DIRECT call into the
    quantized forward kernels — no custom_vjp, nothing to differentiate."""
    gated = wi is not None
    fxp_mode = qfmt is not None
    if fxp_mode and gated:
        raise ValueError("fxp quantization covers plain junctions only — "
                         "the gate epilogue has no single-LUT fixed-point "
                         "form; use the int8 path for gated junctions")
    if fxp_mode and qlut is None:
        raise ValueError("fxp mode needs the baked activation table (qlut)")
    if not fxp_mode and gated and wi_scale is None:
        raise ValueError("gated int8 junction needs wi_scale for the "
                         "second branch")
    single, lead, x3, w5, wi5, b2, E, M, nob, bs, bm, bn = _prep_junction(
        x, w, wi, bias, bm, bn, gated)
    spec = KernelSpec(E=E, gated=gated, act=act, bm=bm, bn=bn,
                      has_bias=bias is not None, interpret=interpret,
                      quant="fxp" if fxp_mode else "int8")
    lift = lambda s: None if s is None else (s[None] if single else s)
    # bias stays fp32 into the quant kernels (the fxp epilogue re-encodes
    # it on the triplet grid; a compute-dtype cast could move the code)
    b = (jnp.zeros((E, nob * bs), jnp.float32) if b2 is None
         else b2.astype(jnp.float32))
    xs = (None if x_scale is None
          else jnp.asarray(x_scale, jnp.float32).reshape(-1))
    with _kernel_scope("junction_matmul", spec):
        if spec.quant == "fxp":
            y = bsm.fwd_fxp(x3, w5, idx, qfmt, qlut, b, bm=spec.bm,
                            bn=spec.bn, interpret=spec.interpret)
        else:
            ws, scales = ((w5, wi5), (w_scale, wi_scale)) if gated else (
                (w5,), (w_scale,))
            y = bsm.junction_fwd_int8(
                x3, ws, idx, tuple(map(lift, scales)), None if gated else b,
                act=spec.act, x_scale=xs, bm=spec.bm, bn=spec.bn,
                interpret=spec.interpret)
    y = y[:, :M]
    return y.reshape(*lead, nob * bs) if single else y


def _prep_junction(x, w, wi, bias, bm, bn, gated):
    """Shared shape/tile/pad preprocessing of the junction wrappers: the
    4-D (single) vs 5-D (expert-batched) squeeze, tile selection and row
    padding."""
    single = w.ndim == 4
    if single:
        lead = x.shape[:-1]
        x3 = x.reshape(1, -1, x.shape[-1])
        w5 = w[None]
        wi5 = wi[None] if gated else None
        b2 = None if bias is None else bias[None]
    else:
        lead = None
        x3, w5, wi5, b2 = x, w, wi, bias
    E, M0, _ = x3.shape
    _, nob, kb, bs, _ = w5.shape
    nib = x3.shape[-1] // bs
    if bm is None or bn is None:
        cbm, cbn = bsm.choose_tiles(M0, nob, kb, bs, nib, x.dtype.itemsize,
                                    E=E, n_weight_operands=2 if gated else 1)
        bm = cbm if bm is None else bm
        bn = cbn if bn is None else bn
    if nob % bn:
        bn = 1
    x3, M = _pad_junction_rows(x3, bm)
    return single, lead, x3, w5, wi5, b2, E, M, nob, bs, bm, bn


def _check_counts(counts, single: bool, E: int) -> None:
    if counts is None:
        return
    if single or counts.shape != (E,) or counts.dtype != jnp.int32:
        raise ValueError(f"counts must be int32 [E={E}] beside 5-D expert "
                         f"weights, got {counts.shape} {counts.dtype}")


def _pad_junction_rows(x, bm):
    M = x.shape[1]
    pad = (-M) % bm
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x, M


def junction_train_update(x, w, idx, rev_ob, rev_t, rev_cnt, *, hyp,
                          wi=None, bias=None, act: str = "none",
                          mom=None, mom_wi=None, mom_b=None, vel=None,
                          vel_wi=None, vel_b=None, health=None,
                          interpret: bool | None = None,
                          bm: int | None = None, bn: int | None = None,
                          counts=None):
    """The fused BP+UP junction — forward y = act(x @ W_sparse + bias)
    exactly like ``junction_matmul``, but the custom_vjp's cotangents for
    the parameter operands (w / wi / bias and their accumulator slots)
    are the optimizer-UPDATED values: the backward runs BP through the
    in-kernel-DMA ``dx`` kernels against the OLD weights, reduces the
    weight gradient into VMEM scratch, and applies the optimizer update
    in the same kernel epilogue, writing the new params/slots through
    ``input_output_aliasing`` — ``dw`` never materializes in HBM (the
    paper's concurrent edge-processor UP stage).  A fused train step
    treats these cotangents as the new parameters (train/steps.py);
    ``optim.FusedOptimizer.merge`` adopts them and tree-maps the dense
    leaves.

    hyp: a hyperparameter row shared by every junction unit — the legacy
    ``[lr, momentum]`` (2,) pair or the full ``(HYP_K,)`` registry row —
    OR, for 5-D expert-batched weights, a per-unit ``[E, 2]`` /
    ``[E, HYP_K]`` table so each unit trains under its own
    hyperparameters in the same launch (the population-search contract:
    E candidate networks sharing one pattern, one kernel grid, E
    distinct hyperparameter rows).  Normalized by
    ``kernels.block_sparse_matmul.normalize_hyp`` and streamed through
    scalar prefetch; the update epilogue reads row ``program_id(0)``.

    The accumulator slots select the optimizer statically (the kernel
    module's slot layout): mom/mom_wi/mom_b alone → SGD(+momentum),
    plus vel/vel_wi/vel_b → Adam (first/second moments m, v); all slots
    fp32 even for bf16 params, all None → plain SGD.

    health: optional f32 zeros of shape ``(E,)`` (``(1,)`` for a single
    4-D junction) switching on the in-kernel divergence detector — the
    operand's *cotangent* under jax.grad is the kernels' per-unit count
    of non-finite update tiles (``> 0`` ⇔ that unit's parameters were
    just destroyed by a non-finite dw).  The forward never reads it; the
    two-pass path has materialized grads to inspect, so the flag only
    exists on this fused path where the gradient otherwise vanishes into
    VMEM.  Requires ``w.dtype == x.dtype``:
    the fused path must not cast weights (a cast would re-materialize
    them and its vjp would corrupt the updated-params contract).

    counts: live rows per unit, as in ``junction_matmul``; a unit with no
    live row still takes its optimizer step (Adam from its moments).
    """
    interpret = _auto_interpret() if interpret is None else interpret
    gated = wi is not None
    if gated and (bias is not None or act != "none"):
        raise ValueError("gated junction fixes act=silu-gate and takes no bias")
    if jnp.issubdtype(w.dtype, jnp.integer) or (
            gated and jnp.issubdtype(wi.dtype, jnp.integer)):
        raise ValueError(
            "junction_train_update refuses quantized (integer-code) "
            "weights — the int8/fxp datapath is inference-only; reload "
            "full-precision weights to train")
    if w.dtype != x.dtype or (gated and wi.dtype != x.dtype) or (
            bias is not None and bias.dtype != x.dtype):
        raise ValueError(
            "junction_train_update requires param dtype == activation dtype "
            f"(got w={w.dtype}, x={x.dtype}) — run the two-pass path for "
            "mixed-precision casts")
    if (mom is None) != (mom_wi is None) and gated:
        raise ValueError("gated junction needs momentum for both branches")
    if (vel is None) != (vel_wi is None) and gated:
        raise ValueError("gated junction needs the Adam v slot for both "
                         "branches")
    if vel is not None and mom is None:
        raise ValueError("the Adam vel slot requires the mom slot too "
                         "(slot layout: w, mom, vel)")
    for name, m in (("mom", mom), ("mom_wi", mom_wi), ("mom_b", mom_b),
                    ("vel", vel), ("vel_wi", vel_wi), ("vel_b", vel_b)):
        if m is not None and m.dtype != jnp.float32:
            raise ValueError(f"{name} must be an fp32 accumulator "
                             f"(got {m.dtype}) — the optimizer state stays "
                             "full-precision even for bf16 params")
    single, lead, x3, w5, wi5, b2, E, M, nob, bs, bm, bn = _prep_junction(
        x, w, wi, bias, bm, bn, gated)
    _check_counts(counts, single, E)
    hyp = bsm.normalize_hyp(hyp, E)
    b = jnp.zeros((E, nob * bs), x.dtype) if b2 is None else b2
    ws = (w5, wi5) if gated else (w5,)

    def _slots(sw, swi, sb):
        """Lift one accumulator-slot family (w slot, gated wi slot, bias
        slot) to the core's tuples, adding the E=1 axis for 4-D calls."""
        if sw is None:
            return (), ()
        sw5 = sw[None] if single else sw
        t = (sw5, swi[None] if single else swi) if gated else (sw5,)
        tb = () if (sb is None or bias is None) else (
            (sb[None] if single else sb),)
        return t, tb

    moms, mom_b_t = _slots(mom, mom_wi, mom_b)
    vels, vel_b_t = _slots(vel, vel_wi, vel_b)
    with_health = health is not None
    if with_health:
        health = jnp.asarray(health, jnp.float32).reshape(-1)
        if health.shape != (E,):
            raise ValueError(
                f"health must be ({E},) f32 zeros (one slot per junction "
                f"unit), got shape {health.shape}")
    else:
        health = jnp.zeros((E,), jnp.float32)
    spec = KernelSpec(E=E, gated=gated, act=act, bm=bm, bn=bn,
                      has_bias=bias is not None, interpret=interpret,
                      with_health=with_health)
    with _kernel_scope("junction_train_update", spec):
        y = _junction_update_core(spec, x3, ws, b, moms, mom_b_t, vels,
                                  vel_b_t, hyp, health, idx, rev_ob, rev_t,
                                  rev_cnt, counts)
    y = y[:, :M]
    return y.reshape(*lead, nob * bs) if single else y


# ------------------------------------------------------------ fixed point
def fxp_qmatmul(a_code, w_code, *, bf: int, bn: int,
                interpret: bool | None = None):
    interpret = _auto_interpret() if interpret is None else interpret
    # ragged shapes pad to the tile inside the kernel wrapper
    return fxpk.qmatmul(a_code, w_code, bf=bf, bn=bn, interpret=interpret)


# ------------------------------------------------------------ LUT sigmoid
def sigmoid_lut(codes, table, interpret: bool | None = None):
    interpret = _auto_interpret() if interpret is None else interpret
    lead = codes.shape[:-1]
    y = slut.lut_lookup(codes.reshape(-1, codes.shape[-1]), table,
                        interpret=interpret)
    return y.reshape(*lead, codes.shape[-1])
