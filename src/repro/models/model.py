"""Model builder: one entry point for all assigned architectures.

``init(cfg, key)``            -> params pytree (fp32 masters, stacked layers)
``forward(cfg, params, batch)``-> (logits, aux) for training
``prefill(cfg, params, batch)``-> (last_logits, cache)
``decode_step(cfg, params, cache, token, pos)`` -> (logits, cache)
``make_cache(cfg, batch, seq)``-> zeroed cache pytree (decode dry-run spec)

Repeated layers are stacked on a leading axis and driven by ``lax.scan`` so
the lowered HLO is O(1) in depth (critical for the 512-device dry-run), with
``jax.checkpoint`` around the block body as the baseline remat policy.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (embed_init, embed_tokens, mlp_apply,
                                 mlp_init, norm_apply, norm_init,
                                 sinusoidal_pos, unembed)
from repro.parallel import hints

Params = dict[str, Any]


# ===================================================================== init
def _block_init(key, cfg: ArchConfig, dtype, kind: str):
    ks = jax.random.split(key, 4)
    if kind == "attn_mlp":
        return {"norm1": norm_init(cfg.d_model, cfg.norm, dtype),
                "attn": attn.attn_init(ks[0], cfg, dtype),
                "norm2": norm_init(cfg.d_model, cfg.norm, dtype),
                "mlp": mlp_init(ks[1], cfg, dtype=dtype)}
    if kind == "attn_moe":
        return {"norm1": norm_init(cfg.d_model, cfg.norm, dtype),
                "attn": attn.attn_init(ks[0], cfg, dtype),
                "norm2": norm_init(cfg.d_model, cfg.norm, dtype),
                "moe": moe_mod.moe_init(ks[1], cfg, dtype)}
    if kind == "mamba1":
        return {"norm": norm_init(cfg.d_model, cfg.norm, dtype),
                "ssm": ssm_mod.mamba1_init(ks[0], cfg, dtype)}
    if kind == "mamba2":
        return {"norm": norm_init(cfg.d_model, cfg.norm, dtype),
                "ssm": ssm_mod.mamba2_init(ks[0], cfg, dtype)}
    if kind == "enc":
        return {"norm1": norm_init(cfg.d_model, cfg.norm, dtype),
                "attn": attn.attn_init(ks[0], cfg, dtype),
                "norm2": norm_init(cfg.d_model, cfg.norm, dtype),
                "mlp": mlp_init(ks[1], cfg, dtype=dtype)}
    if kind == "dec":
        return {"norm1": norm_init(cfg.d_model, cfg.norm, dtype),
                "attn": attn.attn_init(ks[0], cfg, dtype),
                "norm_x": norm_init(cfg.d_model, cfg.norm, dtype),
                "cross": attn.attn_init(ks[1], cfg, dtype, cross=True),
                "norm2": norm_init(cfg.d_model, cfg.norm, dtype),
                "mlp": mlp_init(ks[2], cfg, dtype=dtype)}
    raise ValueError(kind)


def init(cfg: ArchConfig, key) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_layers, k_extra = jax.random.split(key, 3)
    params: Params = {"embed": embed_init(k_emb, cfg, dtype),
                      "final_norm": norm_init(cfg.d_model, cfg.norm, dtype)}

    def stacked(k, n, kind):
        return jax.vmap(lambda kk: _block_init(kk, cfg, dtype, kind))(
            jax.random.split(k, n))

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = stacked(k_layers, cfg.n_layers, "attn_mlp")
    elif fam == "moe":
        nd = cfg.moe.first_dense_layers
        if nd:
            params["dense_layers"] = stacked(k_extra, nd, "attn_mlp")
        params["layers"] = stacked(k_layers, cfg.n_layers - nd, "attn_moe")
    elif fam == "ssm":
        params["layers"] = stacked(k_layers, cfg.n_layers, "mamba1")
    elif fam == "hybrid":
        ev = cfg.hybrid_attn_every
        n_super = cfg.n_layers // ev
        ks = jax.random.split(k_layers, n_super)
        inner = jax.vmap(lambda kk: jax.vmap(
            lambda k2: _block_init(k2, cfg, dtype, "mamba2"))(
                jax.random.split(kk, ev)))(ks)
        params["layers"] = inner                      # [n_super, ev, ...]
        params["shared_attn"] = _block_init(k_extra, cfg, dtype, "attn_mlp")
    elif fam == "audio":
        params["layers"] = stacked(k_layers, cfg.n_layers, "dec")
        params["encoder"] = {
            "layers": stacked(k_extra, cfg.enc_layers, "enc"),
            "norm": norm_init(cfg.d_model, cfg.norm, dtype)}
    else:
        raise ValueError(fam)
    return params


# ============================================================= block applies
def _attn_mlp_block(lp, x, cfg: ArchConfig, positions, cache=None, pos=None,
                    decode=False, kv_override=None):
    """Standard decoder block.  Returns (x, new_cache)."""
    h = norm_apply(lp["norm1"], x, cfg.norm, cfg.norm_eps)
    if decode:
        if cfg.attn_kind == "mla":
            a, new_cache = attn.mla_decode(lp["attn"], h, cfg, cache, pos)
        else:
            a, new_cache = attn.gqa_decode(lp["attn"], h, cfg, cache, pos)
    else:
        if cfg.attn_kind == "mla":
            a, kv = attn.mla_forward(lp["attn"], h, cfg, positions=positions)
            new_cache = {"latent": kv[0], "k_rope": kv[1]}
        else:
            a, kv = attn.gqa_forward(lp["attn"], h, cfg, positions=positions,
                                     kv_override=kv_override)
            new_cache = {"k": kv[0], "v": kv[1]}
    x = x + a
    h = norm_apply(lp["norm2"], x, cfg.norm, cfg.norm_eps)
    m, aux = _ffn(lp, h, cfg)
    return x + m, new_cache, aux


def _ffn(lp, h, cfg: ArchConfig):
    """The block's FFN: (out, aux).  An expert layer's aux is its balance
    loss with its counters (``moe.zero_stats``'s keys); a dense FFN's is
    0.0."""
    if "moe" in lp:
        m, aux, stats = moe_mod.moe_apply(lp["moe"], h, cfg)
        return m, dict(stats, aux=aux)
    return mlp_apply(lp["mlp"], h, cfg), 0.0


def _enc_block(lp, x, cfg: ArchConfig):
    h = norm_apply(lp["norm1"], x, cfg.norm, cfg.norm_eps)
    a, _ = attn.gqa_forward(lp["attn"], h, cfg,
                            positions=jnp.arange(x.shape[1]), causal=False)
    x = x + a
    h = norm_apply(lp["norm2"], x, cfg.norm, cfg.norm_eps)
    return x + mlp_apply(lp["mlp"], h, cfg)


def _dec_block(lp, x, cfg: ArchConfig, positions, enc_kv=None, cache=None,
               pos=None, decode=False):
    """Whisper decoder block: self-attn + cross-attn + mlp."""
    h = norm_apply(lp["norm1"], x, cfg.norm, cfg.norm_eps)
    if decode:
        a, self_cache = attn.gqa_decode(lp["attn"], h, cfg,
                                        {"k": cache["k"], "v": cache["v"]}, pos)
    else:
        a, kv = attn.gqa_forward(lp["attn"], h, cfg, positions=positions)
        self_cache = {"k": kv[0], "v": kv[1]}
    x = x + a
    h = norm_apply(lp["norm_x"], x, cfg.norm, cfg.norm_eps)
    if decode:
        c, _ = attn.gqa_decode(lp["cross"], h, cfg,
                               {"k": cache["ck"], "v": cache["cv"]}, pos,
                               cross=True)
        cross_kv = (cache["ck"], cache["cv"])
    else:
        ck = attn._split_heads(
            jax.numpy.einsum("bsd,df->bsf", enc_kv, lp["cross"]["wk"]["w"].astype(h.dtype)),
            cfg.kv_heads, cfg.head_dim)
        cv = attn._split_heads(
            jax.numpy.einsum("bsd,df->bsf", enc_kv, lp["cross"]["wv"]["w"].astype(h.dtype)),
            cfg.kv_heads, cfg.head_dim)
        c, _ = attn.gqa_forward(lp["cross"], h, cfg, positions=positions,
                                kv_override=(ck, cv))
        cross_kv = (ck, cv)
    x = x + c
    h = norm_apply(lp["norm2"], x, cfg.norm, cfg.norm_eps)
    new_cache = {"k": self_cache["k"], "v": self_cache["v"],
                 "ck": cross_kv[0], "cv": cross_kv[1]}
    return x + mlp_apply(lp["mlp"], h, cfg), new_cache


# ============================================================= full forward
def _maybe_ckpt(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat else fn


def _scan_layers(fn, x, layer_params, cfg, with_cache=None):
    """scan fn over stacked layers; fn(x, lp, cache_i) -> (x, new_cache_i, aux).
    aux sums over layers: a scalar, or an MoE model's balance loss and
    counters (``moe.add_stats``)."""
    moe = cfg.family == "moe"
    aux0 = moe_mod.zero_stats() if moe else 0.0

    def body(carry, inp):
        x, aux_sum = carry
        lp, cache_i = inp
        x, new_cache, aux = fn(x, lp, cache_i)
        x = hints.constrain_tokens3d(x, cfg)   # store carry seq-sharded
        aux_sum = moe_mod.add_stats(aux_sum, aux) if moe else aux_sum + aux
        return (x, aux_sum), new_cache
    body = _maybe_ckpt(body, cfg)
    (x, aux), caches = jax.lax.scan(body, (x, aux0), (layer_params, with_cache))
    return x, caches, aux


def _scan_layers_inplace_cache(fn, x, layer_params, cfg, cache):
    """Static decode-path layer scan: the cache rides in the scan *carry*;
    each layer's cache is sliced out of the stacked dim and written back
    whole (dynamic-update-slice), so a copy of one layer's cache is made
    per layer.  The paged paths use their own scan (_scan_layers_paged),
    which writes only the new rows.

    Passing the cache as scan xs/ys makes XLA allocate a second, stacked
    output cache — for decode_32k that doubles the resident KV bytes
    (§Perf iteration D1: deepseek-7b decode temp 20.8 -> ~4 GiB)."""
    L = jax.tree.leaves(layer_params)[0].shape[0]

    def body(carry, inp):
        x, cache = carry
        lp, i = inp
        ci = jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
            cache)
        x, nc, _ = fn(x, lp, ci)
        cache = jax.tree.map(
            lambda t, u: jax.lax.dynamic_update_index_in_dim(
                t, u.astype(t.dtype), i, 0), cache, nc)
        return (x, cache), None

    (x, cache), _ = jax.lax.scan(body, (x, cache),
                                 (layer_params, jnp.arange(L)))
    return x, cache


def _scan_layers_paged(fn, x, layer_params, pool, page_table):
    """Paged-path layer scan: the pool [L, P, ps, W] rides the carry viewed
    flat as [L*P, ps, W] (a bitcast of the donated buffer), and layer i
    addresses its own pages through ``page_table + i*P``.  So
    fn(x, lp, flat_pool, page_table_i) -> (x, flat_pool, aux) scatters its
    new rows straight into the carry and reads only the pages the table
    names: no layer's pool is sliced out or written back.  Each layer keeps
    its own scratch page (i*P), so free slots still write harmlessly."""
    L, P = jax.tree.leaves(pool)[0].shape[:2]
    flat = jax.tree.map(lambda t: t.reshape(L * P, *t.shape[2:]), pool)

    def body(carry, inp):
        x, flat = carry
        lp, i = inp
        x, flat, _ = fn(x, lp, flat, page_table + i * P)
        return (x, flat), None

    (x, flat), _ = jax.lax.scan(
        body, (x, flat), (layer_params, jnp.arange(L, dtype=jnp.int32)))
    return x, jax.tree.map(lambda t: t.reshape(L, P, *t.shape[1:]), flat)


def _embed_in(cfg: ArchConfig, params, batch, pos0: int = 0):
    """Token (+modality stub) embedding.  Returns (x, positions, text_offset)."""
    tokens = batch["tokens"]
    x = embed_tokens(params["embed"], tokens, cfg)
    off = 0
    if cfg.family == "vlm" and "patches" in batch:
        patches = batch["patches"].astype(cfg.compute_dtype)
        x = jnp.concatenate([patches, x], axis=1)
        off = patches.shape[1]
    S = x.shape[1]
    positions = jnp.arange(pos0, pos0 + S)
    if cfg.family == "audio":
        x = x + jax.lax.dynamic_slice_in_dim(
            params["embed"]["pos"], pos0, S, 0).astype(x.dtype)[None]
    x = hints.constrain_tokens3d(x, cfg)   # anchor: (dp, seq?, None)
    return x, positions, off


def _encode_audio(cfg, params, frames):
    x = frames.astype(cfg.compute_dtype)
    x = x + sinusoidal_pos(x.shape[1], cfg.d_model, x.dtype)[None]
    def body(carry, lp):
        return _enc_block(lp, carry, cfg), None
    body = _maybe_ckpt(body, cfg)
    x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
    return norm_apply(params["encoder"]["norm"], x, cfg.norm, cfg.norm_eps)


def forward(cfg: ArchConfig, params: Params, batch, *, return_cache=False,
            last_only=False, return_hidden=False):
    """Training / prefill forward.  Returns (logits_or_hidden, cache, aux)."""
    x, positions, off = _embed_in(cfg, params, batch)
    fam = cfg.family
    caches = None
    aux = 0.0

    if fam in ("dense", "vlm", "moe"):
        def fn(x, lp, _):
            x, cache, aux = _attn_mlp_block(lp, x, cfg, positions)
            return x, (cache if return_cache else 0), aux
        if fam == "moe" and cfg.moe.first_dense_layers:
            # rematerialised like the scanned layers, so a leading dense
            # layer keeps no activations of its own for the backward
            dense = _maybe_ckpt(lambda lp, x: _attn_mlp_block(
                lp, x, cfg, positions)[:2], cfg)
            dcaches = []
            for i in range(cfg.moe.first_dense_layers):
                lp = jax.tree.map(lambda t: t[i], params["dense_layers"])
                x, dc = dense(lp, x)
                dcaches.append(dc)
        x, caches, aux = _scan_layers(fn, x, params["layers"], cfg)
        if fam == "moe" and cfg.moe.first_dense_layers and return_cache:
            dstack = jax.tree.map(lambda *t: jnp.stack(t), *dcaches)
            caches = {"dense": dstack, "moe": caches}
    elif fam == "ssm":
        def fn(x, lp, _):
            h = norm_apply(lp["norm"], x, cfg.norm, cfg.norm_eps)
            B = x.shape[0]
            zero = {"conv": jnp.zeros((B, cfg.conv_width - 1, cfg.d_inner_), x.dtype),
                    "ssm": jnp.zeros((B, cfg.d_inner_, cfg.ssm_state), jnp.float32)}
            y, cache = ssm_mod.mamba1_apply(lp["ssm"], h, cfg, cache=zero)
            return x + y, (cache if return_cache else 0), 0.0
        x, caches, aux = _scan_layers(fn, x, params["layers"], cfg)
    elif fam == "hybrid":
        shared = params["shared_attn"]
        di, N = cfg.d_inner_, cfg.ssm_state
        H2, hd2 = cfg.ssm_heads, cfg.ssm_head_dim
        def super_fn(carry, lp_super):
            x, aux_s = carry
            h = norm_apply(shared["norm1"], x, cfg.norm, cfg.norm_eps)
            a, kv = attn.gqa_forward(shared["attn"], h, cfg, positions=positions)
            x = x + a
            h = norm_apply(shared["norm2"], x, cfg.norm, cfg.norm_eps)
            x = x + mlp_apply(shared["mlp"], h, cfg)
            def inner(x, lp, _):
                h = norm_apply(lp["norm"], x, cfg.norm, cfg.norm_eps)
                B = x.shape[0]
                zero = {"conv": jnp.zeros((B, cfg.conv_width - 1, di + 2 * N), x.dtype),
                        "ssm": jnp.zeros((B, H2, hd2, N), jnp.float32)}
                y, cache = ssm_mod.mamba2_apply(lp["ssm"], h, cfg, cache=zero)
                return x + y, (cache if return_cache else 0), 0.0
            x, inner_caches, _ = _scan_layers(inner, x, lp_super, cfg)
            x = hints.constrain_tokens3d(x, cfg)
            out = ({"attn": {"k": kv[0], "v": kv[1]}, "ssm": inner_caches}
                   if return_cache else 0)
            return (x, aux_s), out
        (x, aux), caches = jax.lax.scan(super_fn, (x, 0.0), params["layers"])
    elif fam == "audio":
        enc_out = _encode_audio(cfg, params, batch["frames"])
        def fn(x, lp, _):
            x, cache = _dec_block(lp, x, cfg, positions, enc_kv=enc_out)
            return x, (cache if return_cache else 0), 0.0
        x, caches, aux = _scan_layers(fn, x, params["layers"], cfg)
    else:
        raise ValueError(fam)

    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    if last_only:
        x = x[:, -1:]
    if return_hidden:
        return x, caches, (aux, off)
    logits = unembed(params["embed"], x, cfg)
    return logits, caches, (aux, off)


# ============================================================= decode step
def decode_step(cfg: ArchConfig, params: Params, cache, token, pos):
    """One serve step: token [B,1] int32, pos scalar int32.  Returns
    (logits [B,1,V], new_cache)."""
    x = embed_tokens(params["embed"], token, cfg)
    if cfg.family == "audio":
        x = x + jax.lax.dynamic_slice_in_dim(params["embed"]["pos"], pos, 1, 0
                                             ).astype(x.dtype)[None]
    fam = cfg.family
    positions = None

    if fam in ("dense", "vlm", "moe"):
        def fn(x, lp, cache_i):
            x, nc, aux = _attn_mlp_block(lp, x, cfg, positions, cache=cache_i,
                                         pos=pos, decode=True)
            return x, nc, aux
        if fam == "moe" and cfg.moe.first_dense_layers:
            new_d = []
            for i in range(cfg.moe.first_dense_layers):
                lp = jax.tree.map(lambda t: t[i], params["dense_layers"])
                ci = jax.tree.map(lambda t: t[i], cache["dense"])
                x, nc, _ = _attn_mlp_block(lp, x, cfg, positions, cache=ci,
                                           pos=pos, decode=True)
                new_d.append(nc)
            x, moe_cache = _scan_layers_inplace_cache(
                fn, x, params["layers"], cfg, cache["moe"])
            new_cache = {"dense": jax.tree.map(lambda *t: jnp.stack(t), *new_d),
                         "moe": moe_cache}
        else:
            x, new_cache = _scan_layers_inplace_cache(
                fn, x, params["layers"], cfg, cache)
    elif fam == "ssm":
        def fn(x, lp, cache_i):
            h = norm_apply(lp["norm"], x, cfg.norm, cfg.norm_eps)
            y, nc = ssm_mod.mamba1_apply(lp["ssm"], h, cfg, cache=cache_i,
                                         decode=True)
            return x + y, nc, 0.0
        x, new_cache = _scan_layers_inplace_cache(
            fn, x, params["layers"], cfg, cache)
    elif fam == "hybrid":
        shared = params["shared_attn"]
        ns = jax.tree.leaves(params["layers"])[0].shape[0]

        def super_fn(carry, inp):
            x, cache = carry
            lp_super, i = inp
            ci = jax.tree.map(
                lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
                cache)
            h = norm_apply(shared["norm1"], x, cfg.norm, cfg.norm_eps)
            a, ac = attn.gqa_decode(shared["attn"], h, cfg, ci["attn"], pos)
            x = x + a
            h = norm_apply(shared["norm2"], x, cfg.norm, cfg.norm_eps)
            x = x + mlp_apply(shared["mlp"], h, cfg)
            def inner(x, lp, cci):
                h = norm_apply(lp["norm"], x, cfg.norm, cfg.norm_eps)
                y, nc = ssm_mod.mamba2_apply(lp["ssm"], h, cfg, cache=cci,
                                             decode=True)
                return x + y, nc, 0.0
            x, ssm_cache = _scan_layers_inplace_cache(
                inner, x, lp_super, cfg, ci["ssm"])
            new_ci = {"attn": ac, "ssm": ssm_cache}
            cache = jax.tree.map(
                lambda t, u: jax.lax.dynamic_update_index_in_dim(
                    t, u.astype(t.dtype), i, 0), cache, new_ci)
            return (x, cache), None

        (x, new_cache), _ = jax.lax.scan(
            super_fn, (x, cache), (params["layers"], jnp.arange(ns)))
    elif fam == "audio":
        def fn(x, lp, cache_i):
            x, nc = _dec_block(lp, x, cfg, positions, cache=cache_i, pos=pos,
                               decode=True)
            return x, nc, 0.0
        x, new_cache = _scan_layers_inplace_cache(
            fn, x, params["layers"], cfg, cache)
    else:
        raise ValueError(fam)

    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg)
    return logits, new_cache


# ============================================================= paged decode
def paged_supported(cfg: ArchConfig) -> tuple[bool, str]:
    """(ok, reason) — whether the continuous-batching paged-KV decode
    path can serve this config.  Families whose cache carries same-shape
    state leaves (ssm/hybrid conv+ssm state, audio cross-attn KV) and
    the non-GQA cache layouts (MLA latent, sliding ring buffer) stay on
    the static step-locked engine."""
    if cfg.family not in ("dense", "moe"):
        return False, (f"family {cfg.family!r} carries non-seq cache state "
                       "(see cache_seq_axes) — static engine only")
    if cfg.attn_kind != "full":
        return False, (f"attn_kind {cfg.attn_kind!r} — paged decode covers "
                       "the full-attention GQA cache layout")
    if cfg.family == "moe" and cfg.moe.first_dense_layers:
        return False, "moe first_dense_layers splits the cache tree"
    return True, "paged"


def make_paged_cache(cfg: ArchConfig, num_pages: int, page_size: int):
    """Zeroed block-paged KV pool: every seq-axis cache leaf (per
    ``cache_seq_axes``) [L, B, S, Hkv, hd] becomes a pool
    [L, P, ps, Hkv*hd] — memory scales with the page budget
    (tokens-in-flight), not batch x max_len.  Heads and head_dim share
    the lane axis, so a page row is a multiple of 128 lanes at any
    head_dim (stablelm's 80 included) and the paged decode kernel DMAs
    and reads whole rows.  Slot state (page tables, lengths) lives
    outside the tree, in the serve engine."""
    ok, why = paged_supported(cfg)
    if not ok:
        raise ValueError(f"paged cache unsupported: {why}")
    axes = cache_seq_axes(cfg)
    template = make_cache(cfg, 1, 1)

    def mk(ax, t):
        assert ax == 2, (ax, t.shape)
        return jnp.zeros((t.shape[0], num_pages, page_size,
                          math.prod(t.shape[3:])), t.dtype)

    return jax.tree.map(mk, axes, template)


def _attn_block_paged(lp, x, cfg: ArchConfig, cache_i, positions, page_table,
                      *, decode: bool):
    """Paged twin of _attn_mlp_block: attention through the paged pool
    (one layer's pages, addressed by page_table), FFN/MoE unchanged.
    Returns (x, new_cache_i, aux)."""
    h = norm_apply(lp["norm1"], x, cfg.norm, cfg.norm_eps)
    if decode:
        a, new_cache = attn.gqa_decode_paged(lp["attn"], h, cfg, cache_i,
                                             positions, page_table)
    else:
        a, new_cache = attn.gqa_prefill_paged(lp["attn"], h, cfg, cache_i,
                                              positions, page_table)
    x = x + a
    h = norm_apply(lp["norm2"], x, cfg.norm, cfg.norm_eps)
    m, aux = _ffn(lp, h, cfg)
    return x + m, new_cache, aux


def paged_decode_step(cfg: ArchConfig, params: Params, pool, token, positions,
                      page_table):
    """One continuous-batching decode tick: token [B,1] int32, positions
    [B] int32 (per-slot write position — the scalar ``S + i`` of the
    step-locked path replaced by per-slot counters), page_table
    [B, maxp] int32.  Returns (logits [B,1,V], new_pool).  All shapes
    are fixed: slot refills and page-table swaps change data only, so
    the tick compiles exactly once."""
    ok, why = paged_supported(cfg)
    if not ok:
        raise ValueError(f"paged decode unsupported: {why}")
    x = embed_tokens(params["embed"], token, cfg)

    def fn(x, lp, flat, pt):
        return _attn_block_paged(lp, x, cfg, flat, positions, pt,
                                 decode=True)

    x, pool = _scan_layers_paged(fn, x, params["layers"], pool, page_table)
    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg)
    return logits, pool


def paged_prefill_chunk(cfg: ArchConfig, params: Params, pool, tokens, base,
                        page_table_row, chunk_len):
    """Prefill one fixed-size chunk of ONE slot's prompt into the paged
    pool: tokens [1, C] (tail-padded past ``chunk_len``), base scalar
    int32 (absolute position of tokens[0]), page_table_row [maxp].
    Returns (last_logits [1,1,V], new_pool) where last_logits is taken
    at the chunk's final valid position — the seed logits once the last
    chunk lands.  Fixed [1, C] shape: a long prompt becomes several
    chunk calls interleaved with decode ticks instead of one batch-wide
    stall."""
    ok, why = paged_supported(cfg)
    if not ok:
        raise ValueError(f"paged prefill unsupported: {why}")
    C = tokens.shape[1]
    x = embed_tokens(params["embed"], tokens, cfg)
    positions = base + jnp.arange(C)
    pt = page_table_row[None, :]

    def fn(x, lp, flat, pt_i):
        return _attn_block_paged(lp, x, cfg, flat, positions, pt_i,
                                 decode=False)

    x, pool = _scan_layers_paged(fn, x, params["layers"], pool, pt)
    x = norm_apply(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    last = jax.lax.dynamic_slice_in_dim(x, chunk_len - 1, 1, axis=1)
    logits = unembed(params["embed"], last, cfg)
    return logits, pool


# ============================================================= cache specs
def make_cache(cfg: ArchConfig, batch: int, seq: int):
    """Zeroed cache pytree for decode (dry-run ShapeDtypeStruct source)."""
    dt = cfg.compute_dtype
    L = cfg.n_layers
    fam = cfg.family
    if fam in ("dense", "vlm"):
        S = min(seq, cfg.window) if cfg.attn_kind == "sliding" else seq
        kv = lambda: jnp.zeros((L, batch, S, cfg.kv_heads, cfg.head_dim), dt)
        return {"k": kv(), "v": kv()}
    if fam == "moe":
        nd = cfg.moe.first_dense_layers
        if cfg.attn_kind == "mla":
            m = cfg.mla
            mk = lambda n: {"latent": jnp.zeros((n, batch, seq, m.kv_lora_rank), dt),
                            "k_rope": jnp.zeros((n, batch, seq, m.qk_rope_head_dim), dt)}
        else:
            mk = lambda n: {"k": jnp.zeros((n, batch, seq, cfg.kv_heads, cfg.head_dim), dt),
                            "v": jnp.zeros((n, batch, seq, cfg.kv_heads, cfg.head_dim), dt)}
        if nd:
            return {"dense": mk(nd), "moe": mk(L - nd)}
        return mk(L)
    if fam == "ssm":
        return {"conv": jnp.zeros((L, batch, cfg.conv_width - 1, cfg.d_inner_), dt),
                "ssm": jnp.zeros((L, batch, cfg.d_inner_, cfg.ssm_state), jnp.float32)}
    if fam == "hybrid":
        ev = cfg.hybrid_attn_every
        ns = cfg.n_layers // ev
        return {"attn": {"k": jnp.zeros((ns, batch, seq, cfg.kv_heads, cfg.head_dim), dt),
                         "v": jnp.zeros((ns, batch, seq, cfg.kv_heads, cfg.head_dim), dt)},
                "ssm": {"conv": jnp.zeros((ns, ev, batch, cfg.conv_width - 1,
                                           cfg.d_inner_ + 2 * cfg.ssm_state), dt),
                        "ssm": jnp.zeros((ns, ev, batch, cfg.ssm_heads,
                                          cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)}}
    if fam == "audio":
        return {"k": jnp.zeros((L, batch, seq, cfg.kv_heads, cfg.head_dim), dt),
                "v": jnp.zeros((L, batch, seq, cfg.kv_heads, cfg.head_dim), dt),
                "ck": jnp.zeros((L, batch, cfg.enc_frames, cfg.kv_heads, cfg.head_dim), dt),
                "cv": jnp.zeros((L, batch, cfg.enc_frames, cfg.kv_heads, cfg.head_dim), dt)}
    raise ValueError(fam)


def cache_seq_axes(cfg: ArchConfig):
    """Per-leaf placement metadata mirroring ``make_cache``'s structure:
    the axis holding the sequence dimension for leaves that grow with
    decode capacity, or ``-1`` for same-shape state leaves (conv/ssm
    state, cross-attn KV) that are copied wholesale.  Consumed by
    serve/engine.Engine._grow_cache when re-homing a prefill cache — an
    explicit contract instead of guessing the seq dim from shapes."""
    SEQ, STATE = 2, -1
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return {"k": SEQ, "v": SEQ}
    if fam == "moe":
        if cfg.attn_kind == "mla":
            mk = lambda: {"latent": SEQ, "k_rope": SEQ}
        else:
            mk = lambda: {"k": SEQ, "v": SEQ}
        if cfg.moe.first_dense_layers:
            return {"dense": mk(), "moe": mk()}
        return mk()
    if fam == "ssm":
        return {"conv": STATE, "ssm": STATE}
    if fam == "hybrid":
        return {"attn": {"k": SEQ, "v": SEQ},
                "ssm": {"conv": STATE, "ssm": STATE}}
    if fam == "audio":
        return {"k": SEQ, "v": SEQ, "ck": STATE, "cv": STATE}
    raise ValueError(fam)


# ============================================================= loss
def softmax_xent(logits, labels):
    """Vocab-sharding-friendly CE: label logit extracted by fused mask-sum
    (no [T,V] one-hot materialization)."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, len(lf.shape) - 1)
    ll = jnp.sum(jnp.where(iota == labels[..., None], lf, 0.0), axis=-1)
    return lse - ll


def _aux_terms(aux):
    """(balance loss, counters) of forward's aux: an MoE model's carries
    the expert layers' counters (``moe.STATS``) beside the loss."""
    if isinstance(aux, dict):
        return aux["aux"], {k: aux[k] for k in moe_mod.STATS}
    return aux, {}


def loss_fn(cfg: ArchConfig, params: Params, batch):
    """(loss, metrics): mean next-token cross-entropy plus the balance
    loss; metrics holds both, and an MoE model's counters."""
    tokens = batch["tokens"]
    labels = tokens[:, 1:]
    T = labels.shape[1]
    chunk = cfg.loss_chunk
    if chunk:
        c = min(chunk, T)
        while T % c:        # largest divisor of T <= chunk (T=4095 -> 1365)
            c -= 1
        chunk = c if c > 1 else 0
    if not chunk:
        logits, _, (aux, off) = forward(cfg, params, batch)
        aux, counters = _aux_terms(aux)
        lg = logits[:, off:off + T] if off else logits[:, :-1]
        ce = jnp.mean(softmax_xent(lg, labels))
        return ce + aux, {"ce": ce, "aux": aux, **counters}

    # chunked CE: run the trunk once, unembed + CE per sequence chunk under
    # checkpoint so [tokens, vocab] logits never fully materialize (§Perf C2)
    hidden, _, (aux, off) = forward(cfg, params, batch, last_only=False,
                                    return_hidden=True)
    aux, counters = _aux_terms(aux)
    hs = hidden[:, off:off + T] if off else hidden[:, :-1]
    c = chunk
    nc = T // c
    B = hs.shape[0]
    hs = hs.reshape(B, nc, c, -1).swapaxes(0, 1)          # [nc, B, c, D]
    lb = labels.reshape(B, nc, c).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_ce(carry, inp):
        h, l = inp
        logits = unembed(params["embed"], h, cfg)
        return carry + jnp.sum(softmax_xent(logits, l)), None

    total, _ = jax.lax.scan(chunk_ce, jnp.zeros((), jnp.float32), (hs, lb))
    ce = total / (B * T)
    return ce + aux, {"ce": ce, "aux": aux, **counters}
