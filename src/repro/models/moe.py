"""Mixture-of-Experts: a dropless expert layer that knows its share.

The router scores every token over all ``num_experts`` (in float32, as
DeepSeek-V2 publishes it) and keeps ``top_k``.  This device computes the
experts it holds (``MoEConfig.held`` from ``first_held``; all of them by
default), the share of one chip in an expert-parallel deployment; what
the other experts add is left to the chips that hold them.  No token is
dropped: every token-slot routed to a held expert is placed, by index, in
a per-expert buffer ``[held, T_buf, d]`` (its position is its rank among
that expert's slots, token-major), the experts run on the buffer, and the
results are gathered back by the same index and weighted.  An expert can
receive at most one slot per token, so ``T_buf`` (the token count, rounded
up to the row tile) is a bound routing cannot exceed; ``counts[e]`` says
how many rows of expert e's buffer are live.

When the paper's pre-defined sparsity applies to the expert FFNs, one
block pattern (same junction shape) is shared by all experts with
per-expert weights, and the expert matmuls run through the unified
junction entry point ``kernels/ops.junction_matmul`` (weights [E, nob,
kb, bs, bs], ``wi=`` fusing the SwiGLU gate) with the live counts: row
tiles past an expert's count are neither fetched nor computed
(``expert_junction_*`` kernels).  The vmapped gather+einsum loop
(``_expert_apply``) is the jnp engine's path; it computes every row of
the buffer.

The balance loss is Switch/GShard's E * sum_e f_e * p_e over the batch,
or DeepSeek-V2's sequence-wise form (``aux_loss="sequence"``, seq_aux):
the same per sequence, averaged over sequences.  ``moe_apply`` also
returns the layer's counters (``STATS``): token-slots routed to held
experts, rows the computed tiles cover, the largest expert's rows, and
slots dropped (0 by construction).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import sparse_linear as sl
from repro.models.layers import mlp_apply, mlp_init

Params = dict[str, Any]

# the expert kernels' row tile: a multiple of 16 sublanes whose gated
# backward row blocks (dh, g, u at the expert width) fit VMEM
ROW_TILE = 256
STATS = ("moe_routed_rows", "moe_computed_rows", "moe_max_expert_rows",
         "moe_dropped_rows")


def expert_rows(T: int) -> tuple[int, int]:
    """(row tile, buffer rows per expert) for T tokens."""
    bm = min(ROW_TILE, -(-T // 16) * 16)
    return bm, -(-T // bm) * bm


def zero_stats() -> dict:
    """The layer-scan carry of an MoE model: the balance loss and the
    counters, summed over layers (the largest expert's rows: the max)."""
    out = {"aux": jnp.zeros((), jnp.float32)}
    out.update({k: jnp.zeros((), jnp.int32) for k in STATS})
    return out


def add_stats(a: dict, b: dict) -> dict:
    return {k: (jnp.maximum(a[k], b[k]) if k == "moe_max_expert_rows"
                else a[k] + b[k]) for k in a}


def _expert_sparse_ok(cfg: ArchConfig) -> bool:
    sp = cfg.sparsity
    return (sp is not None and sp.applies_to("ffn")
            and cfg.d_model % sp.block == 0 and cfg.moe.d_expert % sp.block == 0
            and cfg.d_model // sp.block >= 2 and cfg.moe.d_expert // sp.block >= 2)


def moe_init(key, cfg: ArchConfig, dtype=jnp.float32, seed: int = 0) -> Params:
    mo, d = cfg.moe, cfg.d_model
    E, F = mo.num_experts, mo.d_expert
    Eh = mo.held_          # the router scores all E; Eh experts live here
    ks = jax.random.split(key, 7)
    scale_in = float(1.0 / np.sqrt(d))
    scale_out = float(1.0 / np.sqrt(F))
    p: Params = {"router": jax.random.normal(ks[0], (d, E), dtype) * scale_in}
    if _expert_sparse_ok(cfg):
        # the paper's technique on the expert FFNs: one block pattern shared
        # by all experts (same junction shape), per-expert weights
        from repro.core.sparsity import make_block_pattern
        sp = cfg.sparsity
        pat_in = make_block_pattern(d, F, sp.density, sp.block, seed=sp.seed)
        pat_out = make_block_pattern(F, d, sp.density, sp.block, seed=sp.seed + 1)
        s_in = float(np.sqrt(2.0 / ((pat_in.fan_in_blocks + pat_in.fan_out_blocks) * sp.block)))
        s_out = float(np.sqrt(2.0 / ((pat_out.fan_in_blocks + pat_out.fan_out_blocks) * sp.block)))
        shp_in = (Eh, pat_in.n_out_blocks, pat_in.fan_in_blocks, sp.block, sp.block)
        shp_out = (Eh, pat_out.n_out_blocks, pat_out.fan_in_blocks, sp.block, sp.block)
        p.update({
            "wi": jax.random.normal(ks[1], shp_in, dtype) * s_in,
            "wg": jax.random.normal(ks[2], shp_in, dtype) * s_in,
            "wo": jax.random.normal(ks[3], shp_out, dtype) * s_out,
            "idx_in": jnp.asarray(pat_in.idx),
            "idx_out": jnp.asarray(pat_out.idx),
            # reverse patterns for the Pallas engine's expert dx kernels
            # (static, non-trainable, shared by all experts like idx_*)
            "rev_in_ob": jnp.asarray(pat_in.rev_ob),
            "rev_in_t": jnp.asarray(pat_in.rev_t),
            "rev_in_cnt": jnp.asarray(pat_in.rev_cnt),
            "rev_out_ob": jnp.asarray(pat_out.rev_ob),
            "rev_out_t": jnp.asarray(pat_out.rev_t),
            "rev_out_cnt": jnp.asarray(pat_out.rev_cnt),
        })
    else:
        p.update({
            "wi": jax.random.normal(ks[1], (Eh, d, F), dtype) * scale_in,
            "wg": jax.random.normal(ks[2], (Eh, d, F), dtype) * scale_in,
            "wo": jax.random.normal(ks[3], (Eh, F, d), dtype) * scale_out,
        })
    if mo.num_shared:
        # d_shared is the *combined* hidden width of the always-on experts
        p["shared"] = mlp_init(ks[4], cfg, d_ff=mo.d_shared, dtype=dtype, seed=seed + 7)
    return p


def _expert_apply(w, idx, x):
    """Batched block-sparse expert matmul (jnp reference path):
    x [E,M,din] -> [E,M,dout].  Accumulates over fan-in slots to avoid
    the kb-times gather blow-up.  This is also the path the dry-run FLOP
    accounting sees (density-scaled einsums)."""
    E, nob, kb, bs, _ = w.shape
    _, M, din = x.shape
    xb = x.reshape(E, M, din // bs, bs)
    wc = w.astype(x.dtype)
    y = None
    for k in range(kb):
        xk = jnp.take(xb, idx[:, k], axis=2)          # [E,M,nob,bs]
        # slot k of every output block: wc[:, :, k] [E, nob, bs, bs]
        part = jnp.einsum("EMob,Eobc->EMoc", xk, wc[:, :, k])
        y = part if y is None else y + part
    return y.reshape(E, M, nob * bs)


def _expert_ffn_pallas(p: Params, xe, counts, bm: int):
    """Expert FFN stack through the unified junction engine:
    xe [E,M,d] -> [E,M,d], unit e live in its first ``counts[e]`` rows.
    Both junctions go through the same ``junction_matmul`` custom_vjp the
    dense-model layers use — the gate (silu(x@wg) * (x@wi)) as ONE fused
    pass via ``wi=``, wo as the plain E-batched configuration — with the
    counts, so dead row tiles cost nothing.  When the fused-update
    context rides in the params dict (train/steps.py injection), both
    junctions run through ``junction_train_update`` instead: the
    per-expert weight gradients are consumed by the in-kernel optimizer
    epilogue (SGD+momentum, or Adam when the vel_* slots ride along) and
    the updated wg/wi/wo come back as their cotangents; an expert with
    no rows still takes its step."""
    from repro.kernels import ops  # local import: kernels optional at runtime
    if "wgq" in p:   # quantized experts (core/quantize.py): inference-only
        if sl.UPDATE_HYP_LEAF in p:
            raise ValueError("quantized expert FFN inside a fused train "
                             "step — the int8 datapath is inference-only")
        h = ops.junction_matmul(
            xe, p["wgq"], p["idx_in"],
            p["rev_in_ob"], p["rev_in_t"], p["rev_in_cnt"], wi=p["wiq"],
            w_scale=p["wg_scale"], wi_scale=p["wi_scale"],
            x_scale=p.get("x_scale_in"))
        return ops.junction_matmul(
            h, p["woq"], p["idx_out"],
            p["rev_out_ob"], p["rev_out_t"], p["rev_out_cnt"],
            w_scale=p["wo_scale"], x_scale=p.get("x_scale_out"))
    if sl.UPDATE_HYP_LEAF in p:
        hyp = p[sl.UPDATE_HYP_LEAF]
        h = ops.junction_train_update(
            xe, p["wg"], p["idx_in"],
            p["rev_in_ob"], p["rev_in_t"], p["rev_in_cnt"], wi=p["wi"],
            hyp=hyp, mom=p.get("mom_wg"), mom_wi=p.get("mom_wi"),
            vel=p.get("vel_wg"), vel_wi=p.get("vel_wi"),
            health=p.get("upd_health_in"), bm=bm, counts=counts)
        return ops.junction_train_update(
            h, p["wo"], p["idx_out"],
            p["rev_out_ob"], p["rev_out_t"], p["rev_out_cnt"],
            hyp=hyp, mom=p.get("mom_wo"), vel=p.get("vel_wo"),
            health=p.get("upd_health_out"), bm=bm, counts=counts)
    h = ops.junction_matmul(
        xe, p["wg"], p["idx_in"],
        p["rev_in_ob"], p["rev_in_t"], p["rev_in_cnt"], wi=p["wi"], bm=bm,
        counts=counts)
    return ops.junction_matmul(
        h, p["wo"], p["idx_out"],
        p["rev_out_ob"], p["rev_out_t"], p["rev_out_cnt"], bm=bm,
        counts=counts)


def route(p: Params, x, cfg: ArchConfig):
    """Router of every expert in float32: x [T,d] -> (probs [T,E],
    weights [T,K], experts [T,K]) — greedy top-k of the softmax,
    renormalised over the k when ``norm_topk_prob``, times the routed
    scale."""
    mo = cfg.moe
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, mo.top_k)
    if mo.norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return probs, top_p * mo.routed_scale, top_e


def balance_loss(probs, top_e, cfg: ArchConfig, batch: int):
    """Switch/GShard's E * sum_e f_e * p_e, f_e the share of routed
    slots and p_e the mean probability of expert e, over the batch or
    (``aux_loss="sequence"``) per sequence and averaged, times the
    weight."""
    mo = cfg.moe
    E, K = mo.num_experts, mo.top_k
    groups = batch if mo.aux_loss == "sequence" else 1
    pr = probs.reshape(groups, -1, E)
    hits = jax.nn.one_hot(top_e, E, dtype=jnp.float32).sum(axis=1)
    f = jnp.mean(hits.reshape(groups, -1, E), axis=1) / K
    per = E * jnp.sum(f * jnp.mean(pr, axis=1), axis=-1)
    return jnp.mean(per) * mo.aux_loss_weight


def dispatch_index(top_e, cfg: ArchConfig, T_buf: int):
    """Where each token-slot goes.  top_e [T,K] -> (dest [T*K]: its row
    in the flat buffer [held*T_buf], or held*T_buf for a slot routed to
    an expert held elsewhere; counts [held] int32).  A slot's row within
    its expert is its rank among that expert's slots, token-major."""
    mo = cfg.moe
    Eh = mo.held_
    local = top_e.reshape(-1) - mo.first_held
    here = (local >= 0) & (local < Eh)
    bucket = jnp.where(here, local, Eh).astype(jnp.int32)
    order = jnp.argsort(bucket, stable=True)
    sizes = jnp.bincount(bucket, length=Eh + 1)
    starts = jnp.cumsum(sizes) - sizes
    rank = jnp.zeros_like(bucket).at[order].set(
        jnp.arange(bucket.shape[0], dtype=jnp.int32) - starts[bucket[order]])
    dest = jnp.where(here, bucket * T_buf + rank, Eh * T_buf)
    return dest, sizes[:Eh].astype(jnp.int32)


def moe_apply(p: Params, x, cfg: ArchConfig):
    """x [B,S,D] -> (y, aux, stats).  The expert matmuls run through the
    engine ``ArchConfig.engine`` resolves to: "pallas" selects the
    expert-batched fused kernels with live counts, "jnp" the reference
    gather+einsum loop over the whole buffer."""
    mo = cfg.moe
    B, S, D = x.shape
    K, Eh = mo.top_k, mo.held_
    T = B * S
    bm, T_buf = expert_rows(T)
    xt = x.reshape(T, D)
    probs, top_w, top_e = route(p, xt, cfg)
    aux = balance_loss(probs, top_e, cfg, B)

    dest, counts = dispatch_index(top_e, cfg, T_buf)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    src = jnp.full((Eh * T_buf,), T, jnp.int32).at[dest].set(tok, mode="drop")
    # row T of the padded tokens is zeros: the buffer's dead rows read it
    xpad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)])
    xd = xpad.at[src].get(mode="promise_in_bounds")
    xd = xd.reshape(Eh, T_buf, D)

    pallas = sl.resolve_engine(cfg.engine) == "pallas"
    if "idx_in" in p:   # pre-defined-sparse experts (the paper's technique)
        if pallas:
            ye = _expert_ffn_pallas(p, xd, counts, bm)
        elif "wgq" in p:   # quantized experts, jnp twin of the int8 kernels
            from repro.core import quantize as qz
            gq = qz.expert_apply_int8(p["wgq"], p["wg_scale"], p["idx_in"],
                                      xd[None], p.get("x_scale_in"))
            uq = qz.expert_apply_int8(p["wiq"], p["wi_scale"], p["idx_in"],
                                      xd[None], p.get("x_scale_in"))
            h = (jax.nn.silu(gq) * uq).astype(x.dtype)
            ye = qz.expert_apply_int8(p["woq"], p["wo_scale"], p["idx_out"],
                                      h, p.get("x_scale_out"))
            ye = ye[0].astype(x.dtype)
        else:
            h = (jax.nn.silu(_expert_apply(p["wg"], p["idx_in"], xd))
                 * _expert_apply(p["wi"], p["idx_in"], xd))
            ye = _expert_apply(p["wo"], p["idx_out"], h)
    else:
        h = (jax.nn.silu(jnp.einsum("EMd,Edf->EMf", xd, p["wg"].astype(x.dtype)))
             * jnp.einsum("EMd,Edf->EMf", xd, p["wi"].astype(x.dtype)))
        ye = jnp.einsum("EMf,Efd->EMd", h, p["wo"].astype(x.dtype))
    # one slot rank at a time, so no [T, K, d] tensor (or its cotangent)
    # is ever live; rows past an expert's count are never read, and a
    # slot held elsewhere points past the buffer and gathers zeros
    ye = ye.reshape(Eh * T_buf, D)
    dest_k = dest.reshape(T, K)
    y = None
    for k in range(K):
        got = jnp.take(ye, dest_k[:, k], axis=0, mode="fill", fill_value=0)
        part = got.astype(jnp.float32) * top_w[:, k:k + 1]
        y = part if y is None else y + part
    y = y.astype(x.dtype)
    y = y.reshape(B, S, D)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], x, cfg)

    routed = jnp.sum(counts)
    if pallas and "idx_in" in p and "wgq" not in p:
        computed = jnp.sum(-(-counts // bm) * bm)
    else:
        computed = jnp.int32(Eh * T_buf)
    stats = {"moe_routed_rows": routed,
             "moe_computed_rows": computed.astype(jnp.int32),
             "moe_max_expert_rows": jnp.max(counts),
             "moe_dropped_rows": (jnp.sum((dest < Eh * T_buf)
                                          .astype(jnp.int32))
                                  - jnp.sum((src < T).astype(jnp.int32)))}
    return y, aux, stats
