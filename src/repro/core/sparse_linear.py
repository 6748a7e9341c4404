"""Pre-defined-sparse linear layer — the paper's junction as a JAX module.

Storage follows the paper's edge-centric layout: weights live as dense
(block, block) tiles indexed by a static block pattern (core/sparsity.py),
exactly like the FPGA's z-wide weight memories indexed through the
interleaver.  Three apply paths:

* ``engine="jnp"``    — gather + einsum, pure jnp.  Used for lowering/dry-run
                        (correct FLOP accounting) and CPU tests.
* ``engine="pallas"`` — the unified edge-bundle Pallas engine
                        (kernels/ops.junction_matmul, the E=1 case of the
                        E-generic kernel family): kb reduction + bias +
                        activation in one kernel, custom_vjp through the
                        fused dx/dw kernels with the reverse weight
                        bundles DMA'd in-kernel.  TPU target; interpret
                        mode off-TPU (tests).
* ``engine="auto"``   — pallas on TPU backends, jnp elsewhere.  This is
                        the default the whole stack runs through
                        (ArchConfig.engine -> models -> train/serve).
* dense fallback      — when a SparsityConfig does not apply (density 1.0,
                        dims not tileable), an ordinary dense matmul.

The neuron-level interleaver composes with the block pattern as a static
permutation — on TPU a layout choice, not a runtime cost (XLA folds static
gathers into the producing op); the bit-faithful neuron-level path lives in
core/paper_net.py.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparsity import BlockPattern, SparsityConfig, make_block_pattern
from repro.parallel import hints

Params = dict[str, Any]

# Static pattern leaves of a sparse junction: int32 scalar-prefetch operands
# of the unified kernels — non-trainable, replicated by parallel/sharding.py
# and skipped by the optimizer.  MoE expert FFNs store the same leaves under
# per-junction names (one shared pattern for the in/out junctions).
PATTERN_LEAVES = ("idx", "rev_ob", "rev_t", "rev_cnt")
MOE_PATTERN_LEAVES = ("idx_in", "idx_out",
                      "rev_in_ob", "rev_in_t", "rev_in_cnt",
                      "rev_out_ob", "rev_out_t", "rev_out_cnt")

# Fused BP+UP context leaves (train/steps.py injects them into every
# pattern-bearing junction dict before differentiating; they exist only
# inside the traced fused train step, never in the stored params tree):
# UPDATE_HYP_LEAF carries the optimizer's hyp row — the legacy
# [lr, momentum] pair or the full (HYP_K,) registry row
# (kernels/block_sparse_matmul.py docstring) — or, for E-batched
# population junctions (src/repro/search/), a per-unit [E, 2] / [E, HYP_K]
# table — broadcast over any layer stacking dims so lax.scan slices it
# per layer.  FUSED_SLOT_NAMES maps each optimizer accumulator slot
# (position i = the kernels' slot i: 0 = SGD momentum / Adam m, 1 = Adam
# v) from each trainable junction weight leaf to that slot's injected
# name; WHICH slots are injected is the kernels' static optimizer switch
# (FusedOptimizer.slot_keys()).  The custom_vjp returns the UPDATED
# params / slots as these leaves' cotangents — the "grads" tree of a
# fused step carries new parameters, not gradients, at junction leaves.
UPDATE_HYP_LEAF = "upd_hyp"
FUSED_MOM = {"w": "mom_w", "b": "mom_b",
             "wi": "mom_wi", "wg": "mom_wg", "wo": "mom_wo"}
FUSED_VEL = {"w": "vel_w", "b": "vel_b",
             "wi": "vel_wi", "wg": "vel_wg", "wo": "vel_wo"}
FUSED_SLOT_NAMES = (FUSED_MOM, FUSED_VEL)
# Divergence-detector leaves: dummy f32 [..., E] zeros injected alongside
# upd_hyp; their cotangents carry the update kernels' per-unit non-finite
# counts (kernels/block_sparse_matmul.py with_health contract).  A single
# junction carries "upd_health" (E=1); a MoE expert-FFN dict carries one
# per fused junction (in/out).  train/steps.py sums them into
# metrics["nonfinite"].
UPDATE_HEALTH_LEAF = "upd_health"
MOE_HEALTH_LEAVES = ("upd_health_in", "upd_health_out")
HEALTH_LEAVES = (UPDATE_HEALTH_LEAF,) + MOE_HEALTH_LEAVES


def is_junction(p) -> bool:
    """A pattern-bearing parameter dict: a single sparse junction ("idx")
    or a MoE expert-FFN pair sharing patterns ("idx_in")."""
    return isinstance(p, dict) and ("idx" in p or "idx_in" in p)


def normalize_slots(slots):
    """Lift every accepted optimizer-state shape to the canonical tuple of
    per-slot trees: None → () (plain SGD), a single params-mirroring tree
    → a 1-tuple (the PR 4 momentum contract), a tuple/list of trees →
    itself (Adam passes (m, v)).  The ambiguity between "one tree" and
    "tuple of trees" is static: params trees are dicts or lists of dicts
    at top level, never tuples."""
    if slots is None:
        return ()
    if isinstance(slots, tuple):
        return slots
    return (slots,)


def inject_update_ctx(params, slots, hyp):
    """Copy of ``params`` with the fused-update context added to every
    junction dict: ``upd_hyp`` (broadcast to the junction's stacking dims,
    derived from its idx leaf) plus the junction's optimizer accumulator
    slots from the mirrored trees in ``slots`` (anything
    ``normalize_slots`` accepts: None → plain SGD, one tree → momentum,
    an (m, v) pair → Adam — slot i lands under its ``FUSED_SLOT_NAMES[i]``
    leaf names, which is how the kernels select the optimizer).  ``hyp``
    is the shared hyp row ((2,) legacy pair or (HYP_K,) registry row) or
    — for E-batched population junctions — a per-unit [E, 2] / [E, HYP_K]
    table; any accepted shape rides through to ``junction_train_update``
    unchanged.  Every junction also gets its dummy health leaf(s) (zeros,
    shape stack + (E,)) so the in-kernel divergence flags come back as
    their cotangents.  Dense leaves ride through untouched — the
    optimizer tree-maps them."""
    slots = normalize_slots(slots)
    if len(slots) > len(FUSED_SLOT_NAMES):
        raise ValueError(f"{len(slots)} accumulator slots, but the kernel "
                         f"contract defines {len(FUSED_SLOT_NAMES)}")

    def rec(p, ms):
        if isinstance(p, dict):
            out = {}
            for k, v in p.items():
                if isinstance(v, (dict, list, tuple)):
                    out[k] = rec(v, tuple(m[k] for m in ms))
                else:
                    out[k] = v
            if is_junction(p):
                if is_quantized(p):
                    raise ValueError(
                        "fused-update context injected into a quantized "
                        "junction — the int8/fxp datapath is "
                        "inference-only; reload full-precision weights "
                        "to train")
                idx = p["idx"] if "idx" in p else p["idx_in"]
                stack = idx.shape[:-2]   # leading layer-scan dims
                out[UPDATE_HYP_LEAF] = jnp.broadcast_to(
                    hyp, stack + tuple(jnp.shape(hyp)))
                wl = p["w"] if "w" in p else p["wg"]
                E = (wl.shape[len(stack)]
                     if wl.ndim - len(stack) == 5 else 1)
                zeros = jnp.zeros(stack + (E,), jnp.float32)
                for hk in (MOE_HEALTH_LEAVES if "idx_in" in p
                           else (UPDATE_HEALTH_LEAF,)):
                    out[hk] = zeros
                for m, names in zip(ms, FUSED_SLOT_NAMES):
                    for k, mk in names.items():
                        if k in p and not isinstance(p[k], dict):
                            out[mk] = m[k]
            return out
        if isinstance(p, (list, tuple)):
            return type(p)(rec(v, tuple(m[i] for m in ms))
                           for i, v in enumerate(p))
        return p
    return rec(params, slots)


def is_sparse(params: Params) -> bool:
    return "idx" in params


def is_quantized(params) -> bool:
    """A junction whose fp weight leaves were replaced by integer codes
    at load time (core/quantize.py): inference-only — the fused-update
    injector and the train paths refuse these dicts."""
    return isinstance(params, dict) and ("wq" in params or "wgq" in params)


def init_dense(key, n_in: int, n_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: float | None = None) -> Params:
    scale = float(scale if scale is not None else 1.0 / np.sqrt(n_in))
    p: Params = {"w": jax.random.normal(key, (n_in, n_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((n_out,), dtype)
    return p


def init_sparse(key, n_in: int, n_out: int, sp: SparsityConfig, *,
                bias: bool = False, dtype=jnp.float32,
                seed: int = 0) -> Params:
    """Glorot-normal init over the *kept* edges (paper Sec. III-C-1: variance
    2/(d_out + d_in) over actual degrees, not the dense widths)."""
    pat = make_block_pattern(n_in, n_out, sp.density, sp.block, seed=seed)
    d_in = pat.fan_in_blocks * pat.block          # actual in-degree per neuron
    d_out = pat.fan_out_blocks * pat.block
    scale = float(np.sqrt(2.0 / (d_in + d_out)))
    shape = (pat.n_out_blocks, pat.fan_in_blocks, pat.block, pat.block)
    p: Params = {
        "w": jax.random.normal(key, shape, dtype) * scale,
        "idx": jnp.asarray(pat.idx),              # static, non-trainable
        "rev_ob": jnp.asarray(pat.rev_ob),
        "rev_t": jnp.asarray(pat.rev_t),
        "rev_cnt": jnp.asarray(pat.rev_cnt),
    }
    if bias:
        p["b"] = jnp.zeros((n_out,), dtype)
    return p


def init_linear(key, n_in: int, n_out: int, *, family: str,
                sp: SparsityConfig | None, bias: bool = False,
                dtype=jnp.float32, seed: int = 0) -> Params:
    """Dense unless the paper's technique applies and the dims tile."""
    if (sp is not None and sp.applies_to(family)
            and n_in % sp.block == 0 and n_out % sp.block == 0
            and n_in // sp.block >= 2):
        return init_sparse(key, n_in, n_out, sp, bias=bias, dtype=dtype, seed=seed)
    return init_dense(key, n_in, n_out, bias=bias, dtype=dtype)


def apply_jnp(params: Params, x: jax.Array) -> jax.Array:
    """y[..., n_out] — per fan-in slot: gather one input block per output
    block, rank-bs matmul, accumulate.

    FLOPs = 2 * M * n_out * (fan_in_blocks * block) — density-scaled, which
    is what the roofline accounting must see.  Looping over the (small)
    fan-in keeps peak memory at O(n_out) per step — gathering all slots at
    once materializes a fan_in_blocks-times-larger tensor (29x d_model for
    qwen2's FFN; §Perf iteration S1).
    """
    w = params["w"]                                  # [nob, kb, bs, bs]
    idx = params["idx"]                              # [nob, kb]
    nob, kb, bs, _ = w.shape
    lead = x.shape[:-1]
    xb = x.reshape(*lead, -1, bs)                    # [..., nib, bs]
    wc = w.astype(x.dtype)
    y = None
    for k in range(kb):                              # kb is small and static
        xk = jnp.take(xb, idx[:, k], axis=-2)        # [..., nob, bs]
        part = jnp.einsum("...ob,obc->...oc", xk, wc[:, k])
        y = part if y is None else y + part
    y = y.reshape(*lead, nob * bs)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def apply_dense(params: Params, x: jax.Array) -> jax.Array:
    y = x @ params["w"].astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


def resolve_engine(engine: str) -> str:
    """'auto' -> 'pallas' on TPU backends, 'jnp' elsewhere.  Resolve once
    at step-build time (train/steps.py) so the traced graph is stable."""
    from repro.kernels import ops  # local import: kernels optional at runtime
    return ops.resolve_engine(engine)


def _with_act(y: jax.Array, act: str) -> jax.Array:
    """Epilogue for the jnp/dense paths — the single activation table the
    Pallas engine fuses, so the engines can never diverge formula-wise."""
    if act == "none":
        return y
    from repro.kernels import block_sparse_matmul as bsm
    return bsm.act_fwd(y, act).astype(y.dtype)


def apply(params: Params, x: jax.Array, *, engine: str = "auto",
          act: str = "none") -> jax.Array:
    """y = act(x @ W + b) through the configured execution engine.

    A junction dict carrying the injected fused-update context
    (``UPDATE_HYP_LEAF``; only ever present inside a fused train step's
    trace) routes through ``junction_train_update``: forward identical,
    backward returns the updated params as the weight cotangents."""
    if not is_sparse(params):
        return _with_act(apply_dense(params, x), act)
    quantized = is_quantized(params)
    if quantized and UPDATE_HYP_LEAF in params:
        raise ValueError("quantized junction inside a fused train step — "
                         "the int8/fxp datapath is inference-only")
    if resolve_engine(engine) == "pallas":
        from repro.kernels import ops  # local import: kernels optional at runtime
        if UPDATE_HYP_LEAF in params:
            if hints.current_mesh() is not None:
                raise ValueError(
                    "fused BP+UP under a device mesh: each device would "
                    "apply the update from its own partial gradient — "
                    "run the two-pass step (ArchConfig.fused_update off)")
            return ops.junction_train_update(
                x, params["w"], params["idx"], params["rev_ob"],
                params["rev_t"], params["rev_cnt"], bias=params.get("b"),
                act=act, hyp=params[UPDATE_HYP_LEAF],
                mom=params.get("mom_w"), mom_b=params.get("mom_b"),
                vel=params.get("vel_w"), vel_b=params.get("vel_b"),
                health=params.get(UPDATE_HEALTH_LEAF))
        if quantized:
            return ops.junction_matmul(
                x, params["wq"], params["idx"], params["rev_ob"],
                params["rev_t"], params["rev_cnt"], bias=params.get("b"),
                act=act, w_scale=params.get("w_scale"),
                x_scale=params.get("x_scale"), qfmt=params.get("qfmt"),
                qlut=params.get("qlut"))

        def junction(x, w, idx, rev_ob, rev_t, rev_cnt, *bias):
            return ops.junction_matmul(x, w, idx, rev_ob, rev_t, rev_cnt,
                                       bias=bias[0] if bias else None,
                                       act=act)

        return hints.shard_rows(
            junction, x, params["w"], params["idx"], params["rev_ob"],
            params["rev_t"], params["rev_cnt"],
            *((params["b"],) if "b" in params else ()))
    if quantized:
        from repro.core import quantize as qz  # local: avoids import cycle
        return qz.apply_quant_jnp(params, x, act=act)
    return _with_act(apply_jnp(params, x), act)


def density(params: Params) -> float:
    if not is_sparse(params):
        return 1.0
    kb = (params["w"] if "w" in params else params["wq"]).shape[1]
    # rev_ob's leading dim IS n_in_blocks (built per input block by
    # reverse_block_pattern) — a static shape, so no host sync in jitted
    # contexts, and exact even when the highest input block is unused.
    n_in_blocks = params["rev_ob"].shape[0]
    return kb / n_in_blocks


def n_weights(params: Params) -> int:
    return int(np.prod((params["w"] if "w" in params
                        else params["wq"]).shape))
