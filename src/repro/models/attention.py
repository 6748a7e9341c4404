"""Attention: GQA / MLA / sliding-window, train+prefill+decode paths.

Memory discipline: full-sequence attention is computed with an
online-softmax scan over KV chunks (flash-attention semantics in plain
lax.scan — the Pallas kernel in kernels/flash_attention.py is the TPU
drop-in).  Decode attends over the whole cache with masked softmax; with
the cache sequence dimension sharded over the "model" mesh axis the XLA
SPMD partitioner turns the softmax/contraction reductions into tiny
all-reduces — flash-decoding for free (DESIGN.md Sec. 5).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import sparse_linear as sl
from repro.models.layers import mla_rope, norm_apply, norm_init, rope

NEG_INF = -1e30
Params = dict[str, Any]


# =============================================================== init
def attn_init(key, cfg: ArchConfig, dtype=jnp.float32, cross: bool = False,
              seed: int = 0) -> Params:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    sp = cfg.sparsity
    ks = jax.random.split(key, 6)
    if cfg.attn_kind == "mla" and not cross:
        m = cfg.mla
        qd = H * (m.qk_nope_head_dim + m.qk_rope_head_dim)
        p: Params = {
            "wq": sl.init_linear(ks[0], d, qd, family="attn", sp=sp, dtype=dtype, seed=seed),
            "wkv_a": sl.init_dense(ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype=dtype),
            "kv_norm": norm_init(m.kv_lora_rank, "rmsnorm", dtype),
            "wkv_b": sl.init_dense(ks[2], m.kv_lora_rank,
                                   H * (m.qk_nope_head_dim + m.v_head_dim), dtype=dtype),
            "wo": sl.init_linear(ks[3], H * m.v_head_dim, d, family="attn", sp=sp,
                                 dtype=dtype, seed=seed + 1),
        }
        return p
    p = {
        "wq": sl.init_linear(ks[0], d, H * hd, family="attn", sp=sp,
                             bias=cfg.qkv_bias, dtype=dtype, seed=seed),
        "wk": sl.init_dense(ks[1], d, Hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": sl.init_dense(ks[2], d, Hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": sl.init_linear(ks[3], H * hd, d, family="attn", sp=sp,
                             dtype=dtype, seed=seed + 1),
    }
    return p


# =============================================================== core math
def _split_heads(x, n_heads, hd):
    return x.reshape(*x.shape[:-1], n_heads, hd)


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      chunk: int = 1024, q_pos=None, kv_pos=None,
                      scale: float | None = None):
    """Online-softmax attention.  q [B,Sq,H,D]; k,v [B,Sk,Hkv,D]; scores
    scaled by ``scale`` (default 1/sqrt(D)).

    Scans KV chunks carrying (running max, normalizer, weighted acc) in fp32
    — numerically identical to monolithic softmax, O(Sq*chunk) live memory.
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    scale = 1.0 / np.sqrt(D) if scale is None else scale
    chunk = min(chunk, Sk)
    if Sk % chunk:  # pad KV to a chunk multiple; padding masked below
        pad = chunk - Sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nck = k.shape[1] // chunk
    if q_pos is None:
        q_pos = jnp.arange(Sq)
    if kv_pos is None:
        kv_pos = jnp.arange(Sk)
    kv_pos = jnp.pad(kv_pos, (0, k.shape[1] - Sk), constant_values=Sk + 10**9)

    q5 = q.reshape(B, Sq, Hkv, rep, D)
    kc = k.reshape(B, nck, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nck, chunk, Hkv, D).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(nck, chunk)

    def step(carry, inp):
        m, l, acc = carry
        kj, vj, pj = inp
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q5, kj,
                       preferred_element_type=jnp.float32) * scale
        mask = pj[None, None, None, None, :] <= Sk + 10**8  # padding mask
        if causal:
            mask = mask & (q_pos[None, None, None, :, None]
                           >= pj[None, None, None, None, :])
        if window:
            mask = mask & (q_pos[None, None, None, :, None]
                           - pj[None, None, None, None, :] < window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        upd = jnp.einsum("bgrqk,bkgd->bgrqd", p.astype(q.dtype), vj,
                         preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + upd
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, rep, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, rep, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, rep, Sq, D), jnp.float32)
    # recompute scores in the backward pass (flash-attention style): without
    # this the scan stashes per-chunk [B,H,Sq,ck] score tensors for autodiff
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), (m0, l0, a0),
                                  (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


def causal_block_attention(q, k, v, *, chunk: int, positions, scale=None):
    """Causal self-attention of a sequence over its own keys (q and k at
    the same ``positions``): the queries in blocks of ``chunk``, each
    against the keys up to its block's end, so the blocks past the
    diagonal are neither computed nor stored; each block is
    checkpointed, so a backward keeps one block's softmax state live
    instead of every kv chunk's accumulator over the whole sequence."""
    S = q.shape[1]
    if S <= chunk or S % chunk:
        return chunked_attention(q, k, v, causal=True, chunk=chunk,
                                 q_pos=positions, kv_pos=positions,
                                 scale=scale)
    block = jax.checkpoint(functools.partial(
        chunked_attention, causal=True, chunk=chunk, scale=scale))
    outs = []
    for i in range(S // chunk):
        lo, hi = i * chunk, (i + 1) * chunk
        outs.append(block(q[:, lo:hi], k[:, :hi], v[:, :hi],
                          q_pos=positions[lo:hi], kv_pos=positions[:hi]))
    return jnp.concatenate(outs, axis=1)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0):
    """q [B,1,H,D]; caches [B,S,Hkv,D]; pos: scalar current position.

    With S sharded over the model axis this lowers to local partial
    softmax + tiny all-reduces (flash-decoding).  For ring-buffer (sliding
    window) caches S == window and every slot written so far is valid.
    """
    B, _, H, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    rep = H // Hkv
    scale = 1.0 / np.sqrt(D)
    q5 = q.reshape(B, 1, Hkv, rep, D)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q5, k_cache,
                   preferred_element_type=jnp.float32) * scale
    idx = jnp.arange(S)
    if window:  # ring buffer: slots 0..min(pos, S-1) valid
        valid = (idx <= pos) | (pos >= S)
    else:
        valid = idx <= pos
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bgrqk,bkgd->bgrqd", (p / l).astype(q.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, D).astype(q.dtype)


# =============================================================== GQA paths
def gqa_forward(p: Params, x, cfg: ArchConfig, *, positions, causal=True,
                kv_override=None):
    """Train/prefill/encoder self-attention (full sequence).

    Returns (out, (k, v)) — k/v handed to the caller for cache building.
    ``kv_override`` supplies encoder K/V for cross-attention.
    """
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = _split_heads(sl.apply(p["wq"], x, engine=cfg.engine), H, hd)
    if kv_override is None:
        k = _split_heads(sl.apply(p["wk"], x, engine=cfg.engine), Hkv, hd)
        v = _split_heads(sl.apply(p["wv"], x, engine=cfg.engine), Hkv, hd)
        if cfg.family != "audio":  # whisper uses absolute positions, no rope
            q = rope(q, positions, cfg.rope_theta, cfg.partial_rotary)
            k = rope(k, positions, cfg.rope_theta, cfg.partial_rotary)
    else:
        k, v = kv_override
        causal = False
    window = cfg.window if cfg.attn_kind == "sliding" else 0
    kv_pos = positions if kv_override is None else None
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            chunk=cfg.attn_chunk, q_pos=positions, kv_pos=kv_pos)
    out = sl.apply(p["wo"], out.reshape(B, S, H * hd), engine=cfg.engine)
    return out, (k, v)


def gqa_decode(p: Params, x, cfg: ArchConfig, cache: dict, pos,
               cross: bool = False):
    """Single-token decode.  cache: {"k": [B,S,Hkv,hd], "v": ...}.

    Sliding-window archs use a ring buffer (S == window, slot = pos % S).
    Returns (out, new_cache).
    """
    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = _split_heads(sl.apply(p["wq"], x, engine=cfg.engine), H, hd)
    if not cross:
        k_new = _split_heads(sl.apply(p["wk"], x, engine=cfg.engine), Hkv, hd)
        v_new = _split_heads(sl.apply(p["wv"], x, engine=cfg.engine), Hkv, hd)
        if cfg.family != "audio":
            pos_arr = jnp.full((1,), pos)
            q = rope(q, pos_arr, cfg.rope_theta, cfg.partial_rotary)
            k_new = rope(k_new, pos_arr, cfg.rope_theta, cfg.partial_rotary)
        S = cache["k"].shape[1]
        sliding = cfg.attn_kind == "sliding"
        slot = pos % S if sliding else pos
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), slot, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), slot, 1)
        out = decode_attention(q, k_cache, v_cache, pos, window=S if sliding else 0)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        # cross attention: every encoder slot valid, cache is read-only
        S = cache["k"].shape[1]
        out = decode_attention(q, cache["k"], cache["v"], jnp.asarray(S - 1))
        new_cache = cache
    out = sl.apply(p["wo"], out.reshape(B, 1, H * hd), engine=cfg.engine)
    return out, new_cache


# =============================================================== paged paths
def paged_kv_update(cache: dict, k_new, v_new, positions, page_table,
                    keys=("k", "v")):
    """Scatter new KV rows into the block-paged pool.

    cache: {"k": [P, ps, Hkv*hd], "v": ...} (the page pool, heads x
    head_dim merged on the lane axis — models/model.make_paged_cache; the
    paged model paths pass the stacked pool viewed flat, with page ids
    offset to the layer's pages); k_new/v_new [B, S, Hkv, hd] — tokens
    to write; positions [B, S] — their absolute positions; page_table
    [B, maxp] — pool page ids in token order.  Token at position t lands
    in page page_table[b, t//ps] at offset t % ps, so a slot refill is a
    page-table swap, never a cache copy.  Free/prefilling slots are
    pointed at the reserved scratch page by the engine, so their writes
    are harmless."""
    ps = cache[keys[0]].shape[1]
    pid = jnp.take_along_axis(page_table, positions // ps, axis=1)   # [B, S]
    off = positions % ps
    pid, off = pid.reshape(-1), off.reshape(-1)
    out = dict(cache)
    for key, new in zip(keys, (k_new, v_new)):
        flat = new.reshape(pid.shape[0], -1).astype(cache[key].dtype)
        out[key] = cache[key].at[pid, off].set(flat)
    return out


def paged_decode_attention(q, k_pool, v_pool, page_table, seq_lens, *,
                           engine: str = "jnp"):
    """q [B,1,H,D]; pools [P,ps,Hkv*D]; page_table [B,maxp];
    seq_lens [B] (valid tokens per slot).  Routes through the Pallas
    flash_decode kernel under engine="pallas" (page table on scalar
    prefetch, per-page HBM→VMEM DMA) and the gather+masked-softmax
    reference otherwise.  Returns [B,1,H,D]."""
    from repro.kernels import flash_attention as fa
    B, _, H, D = q.shape
    Hkv = k_pool.shape[2] // D
    rep = H // Hkv
    qf = q.reshape(B, Hkv, rep, D)
    if engine == "pallas":
        out = fa.flash_decode(qf, k_pool, v_pool, page_table, seq_lens)
    else:
        out = fa.paged_decode_ref(qf, k_pool, v_pool, page_table, seq_lens)
    return out.reshape(B, 1, H, D)


def gqa_decode_paged(p: Params, x, cfg: ArchConfig, cache: dict, positions,
                     page_table):
    """Continuous-batching single-token decode over the paged pool.

    x [B,1,d]; positions [B] — per-slot write position (the cache holds
    ``positions[b]`` tokens before this call); page_table [B, maxp].
    Returns (out, new_cache).  Unlike gqa_decode there is no scalar
    step: every slot carries its own counter, so a mid-tick refill only
    changes the prefetched integers."""
    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = _split_heads(sl.apply(p["wq"], x, engine=cfg.engine), H, hd)
    k_new = _split_heads(sl.apply(p["wk"], x, engine=cfg.engine), Hkv, hd)
    v_new = _split_heads(sl.apply(p["wv"], x, engine=cfg.engine), Hkv, hd)
    pos2d = positions[:, None]                                   # [B, 1]
    q = rope(q, pos2d, cfg.rope_theta, cfg.partial_rotary)
    k_new = rope(k_new, pos2d, cfg.rope_theta, cfg.partial_rotary)
    new_cache = paged_kv_update(cache, k_new, v_new, pos2d, page_table)
    out = paged_decode_attention(q, new_cache["k"], new_cache["v"],
                                 page_table, positions + 1, engine=cfg.engine)
    out = sl.apply(p["wo"], out.reshape(B, 1, H * hd), engine=cfg.engine)
    return out, new_cache


def gqa_prefill_paged(p: Params, x, cfg: ArchConfig, cache: dict, positions,
                      page_table):
    """Chunked-prefill attention for one slot: x [1,C,d] (a fixed-size
    prompt chunk, possibly tail-padded), positions [C] absolute chunk
    positions, page_table [1, maxp].  Writes the chunk's KV into the
    slot's pages, then attends causally over the gathered pages (earlier
    chunks included) via chunked_attention with the gathered index as
    kv position — padded tail tokens land past the prompt and are
    overwritten by decode before they are ever unmasked."""
    B, C, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q = _split_heads(sl.apply(p["wq"], x, engine=cfg.engine), H, hd)
    k_new = _split_heads(sl.apply(p["wk"], x, engine=cfg.engine), Hkv, hd)
    v_new = _split_heads(sl.apply(p["wv"], x, engine=cfg.engine), Hkv, hd)
    q = rope(q, positions, cfg.rope_theta, cfg.partial_rotary)
    k_new = rope(k_new, positions, cfg.rope_theta, cfg.partial_rotary)
    new_cache = paged_kv_update(cache, k_new, v_new, positions[None, :],
                                page_table)
    ps = new_cache["k"].shape[1]
    maxp = page_table.shape[1]
    kg = new_cache["k"][page_table[0]].reshape(1, maxp * ps, Hkv, hd)
    vg = new_cache["v"][page_table[0]].reshape(1, maxp * ps, Hkv, hd)
    out = chunked_attention(q, kg, vg, causal=True, chunk=cfg.attn_chunk,
                            q_pos=positions, kv_pos=jnp.arange(maxp * ps))
    out = sl.apply(p["wo"], out.reshape(B, C, H * hd), engine=cfg.engine)
    return out, new_cache


# =============================================================== MLA paths
def mla_forward(p: Params, x, cfg: ArchConfig, *, positions):
    """DeepSeek-V2 multi-head latent attention, expanded form (train/prefill).
    Rotary frequencies and the score scale come from ``mla_rope`` (YaRN
    when the config scales its rope).

    Returns (out, (latent, k_rope)) for the compressed cache."""
    B, S, _ = x.shape
    m, H = cfg.mla, cfg.n_heads
    nope, rd, vd, lora = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                          m.v_head_dim, m.kv_lora_rank)
    inv_freq, scale = mla_rope(cfg)
    q = _split_heads(sl.apply(p["wq"], x, engine=cfg.engine), H, nope + rd)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope(q_rope, positions, cfg.rope_theta, inv_freq=inv_freq)

    a = sl.apply_dense(p["wkv_a"], x)                       # [B,S,lora+rd]
    latent = norm_apply(p["kv_norm"], a[..., :lora], "rmsnorm", cfg.norm_eps)
    k_rope = rope(a[..., lora:][:, :, None, :], positions, cfg.rope_theta,
                  inv_freq=inv_freq)                        # [B,S,1,rd]

    kvb = sl.apply_dense(p["wkv_b"], latent)                # [B,S,H*(nope+vd)]
    kvb = kvb.reshape(B, S, H, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rd))], -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    # pad v to qk dim for the shared chunked kernel, slice after
    v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, nope + rd - vd)))
    out = causal_block_attention(qf, k, v_pad, chunk=cfg.attn_chunk,
                                 positions=positions, scale=scale)[..., :vd]
    out = sl.apply(p["wo"], out.reshape(B, S, H * vd), engine=cfg.engine)
    return out, (latent, k_rope[:, :, 0, :])


def mla_decode(p: Params, x, cfg: ArchConfig, cache: dict, pos):
    """Absorbed-form MLA decode: attention scored directly in latent space —
    the cache is [B,S,lora] + [B,S,rd] (the paper-stated memory win)."""
    B = x.shape[0]
    m, H = cfg.mla, cfg.n_heads
    nope, rd, vd, lora = (m.qk_nope_head_dim, m.qk_rope_head_dim,
                          m.v_head_dim, m.kv_lora_rank)
    inv_freq, scale = mla_rope(cfg)
    q = _split_heads(sl.apply(p["wq"], x, engine=cfg.engine), H, nope + rd)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    pos_arr = jnp.full((1,), pos)
    q_rope = rope(q_rope, pos_arr, cfg.rope_theta, inv_freq=inv_freq)

    a = sl.apply_dense(p["wkv_a"], x)
    lat_new = norm_apply(p["kv_norm"], a[..., :lora], "rmsnorm", cfg.norm_eps)
    kr_new = rope(a[..., lora:][:, :, None, :], pos_arr, cfg.rope_theta,
                  inv_freq=inv_freq)[:, :, 0, :]
    lat = jax.lax.dynamic_update_slice_in_dim(
        cache["latent"], lat_new.astype(cache["latent"].dtype), pos, 1)
    kr = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos, 1)

    wkv_b = p["wkv_b"]["w"].reshape(lora, H, nope + vd).astype(x.dtype)
    w_uk, w_uv = wkv_b[..., :nope], wkv_b[..., nope:]
    # absorb W_UK into q: [B,1,H,lora]
    q_abs = jnp.einsum("bqhn,lhn->bqhl", q_nope, w_uk)
    s = (jnp.einsum("bqhl,bsl->bhqs", q_abs, lat, preferred_element_type=jnp.float32)
         + jnp.einsum("bqhr,bsr->bhqs", q_rope, kr, preferred_element_type=jnp.float32))
    s = s * scale
    valid = jnp.arange(lat.shape[1]) <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhqs,bsl->bqhl", pr, lat)
    out = jnp.einsum("bqhl,lhv->bqhv", o_lat, w_uv)
    out = sl.apply(p["wo"], out.reshape(B, 1, H * vd), engine=cfg.engine)
    return out, {"latent": lat, "k_rope": kr}
