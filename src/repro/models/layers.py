"""Common model pieces: norms, rotary embeddings, token embedding, MLP.

Pure-functional: ``*_init(key, ...) -> params dict`` and ``*_apply``.
Compute runs in ``cfg.dtype`` (bf16), parameters live in ``param_dtype``
(fp32 master copies for the optimizer).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import sparse_linear as sl
from repro.configs.base import ArchConfig


# ------------------------------------------------------------------ norms
def norm_init(d: int, kind: str, dtype=jnp.float32):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def norm_apply(p, x, kind: str, eps: float):
    """f32 *accumulation* (reduction dtype), bf16 elementwise.

    Materializing ``x.astype(f32)`` looks equivalent, but under scan+remat
    XLA hoists that convert out of the backward loop, materializing an f32
    image of the whole [L, B, S, D] saved-carry stack (10 GiB for qwen2
    train — §Perf iteration C3).  Reduction-dtype accumulation keeps every
    full-size tensor bf16."""
    if kind == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True, dtype=jnp.float32)
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
        var = ms - jnp.square(mu)
        inv = jax.lax.rsqrt(var + eps)
        y = (x - mu.astype(x.dtype)) * inv.astype(x.dtype)
        y = y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
        inv = jax.lax.rsqrt(ms + eps)
        y = x * inv.astype(x.dtype) * p["scale"].astype(x.dtype)
    return y.astype(x.dtype)


# ------------------------------------------------------------------ rotary
def rope(x: jax.Array, positions: jax.Array, theta: float,
         partial: float = 1.0, inv_freq=None) -> jax.Array:
    """x [..., S, H, D]; positions [..., S] (broadcastable).  Rotates the
    first ``partial * D`` dims (stablelm-style partial rotary), pair
    (i, i + rot/2) at frequency ``inv_freq[i]`` (default theta^(-2i/rot))."""
    d = x.shape[-1]
    rot = int(d * partial)
    rot -= rot % 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    if inv_freq is None:
        freqs = jnp.exp(-np.log(theta) * jnp.arange(half, dtype=jnp.float32)
                        / half)
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    ang = positions[..., None].astype(jnp.float32) * freqs     # [..., S, half]
    cos = jnp.cos(ang)[..., None, :].astype(x.dtype)           # [..., S, 1, half]
    sin = jnp.sin(ang)[..., None, :].astype(x.dtype)
    x1, x2 = xr[..., :half], xr[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out, xp], axis=-1) if rot < d else out


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, sc) -> np.ndarray:
    """YaRN rotary frequencies (HF ``DeepseekV2YarnRotaryEmbedding``):
    theta^(-2i/dim) below the correction range, divided by ``factor``
    above it, a linear ramp between.  The range runs from
    floor(d(beta_fast)) to ceil(d(beta_slow)), where d(r) =
    dim ln(L / (2 pi r)) / (2 ln theta) and L the original context."""
    def corr(r):
        return (dim * math.log(sc.original_max_position / (r * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(corr(sc.beta_fast)), 0)
    hi = min(math.ceil(corr(sc.beta_slow)), dim - 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return (extra / sc.factor * (1 - keep) + extra * keep).astype(np.float32)


def mla_rope(cfg: ArchConfig) -> tuple:
    """(inv_freq or None, softmax scale) of latent attention: plain RoPE
    and 1/sqrt(qk dim), or YaRN's frequencies and the score scale
    mscale(factor, mscale_all_dim)^2 / sqrt(qk dim).  The cos/sin factor
    mscale(mscale) / mscale(mscale_all_dim) must be 1 (DeepSeek-V2 sets
    both to the same value), so it is not applied."""
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    sc = cfg.rope_scaling
    if sc is None:
        return None, scale
    if yarn_mscale(sc.factor, sc.mscale) != yarn_mscale(sc.factor,
                                                        sc.mscale_all_dim):
        raise ValueError("YaRN with mscale != mscale_all_dim scales cos/sin;"
                         " not supported")
    inv = yarn_inv_freq(m.qk_rope_head_dim, cfg.rope_theta, sc)
    if sc.mscale_all_dim:
        scale *= yarn_mscale(sc.factor, sc.mscale_all_dim) ** 2
    return inv, scale


def sinusoidal_pos(seq: int, d: int, dtype) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ------------------------------------------------------------------ embed
def embed_init(key, cfg: ArchConfig, dtype=jnp.float32):
    scale = float(1.0 / np.sqrt(cfg.d_model))
    p = {"tok": jax.random.normal(key, (cfg.vocab, cfg.d_model), dtype) * scale}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["out"] = jax.random.normal(k2, (cfg.d_model, cfg.vocab), dtype) * scale
    if cfg.family == "audio":  # learned decoder positions (whisper)
        k3 = jax.random.fold_in(key, 2)
        p["pos"] = jax.random.normal(k3, (cfg.max_seq, cfg.d_model), dtype) * 0.02
    return p


def embed_tokens(p, tokens, cfg: ArchConfig):
    return jnp.take(p["tok"], tokens, axis=0).astype(cfg.compute_dtype)


def unembed(p, x, cfg: ArchConfig):
    w = p["tok"].T if cfg.tie_embeddings else p["out"]
    return jnp.einsum("...d,dv->...v", x, w.astype(x.dtype))


# ------------------------------------------------------------------ MLP
def mlp_init(key, cfg: ArchConfig, d_ff: int | None = None, dtype=jnp.float32,
             seed: int = 0):
    """(Gated) MLP; projections become pre-defined-sparse when the paper's
    technique is enabled for the 'ffn' family."""
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    sp = cfg.sparsity
    p = {"wi": sl.init_linear(ks[0], d, f, family="ffn", sp=sp, dtype=dtype, seed=seed),
         "wo": sl.init_linear(ks[2], f, d, family="ffn", sp=sp, dtype=dtype, seed=seed + 1)}
    if cfg.act == "silu":
        p["wg"] = sl.init_linear(ks[1], d, f, family="ffn", sp=sp, dtype=dtype, seed=seed + 2)
    return p


def mlp_apply(p, x, cfg: ArchConfig):
    """The activation rides as a fused epilogue of the producing linear —
    on the Pallas engine it runs inside the kernel (the paper's FF-stage
    activation fused into the edge pipeline); on the jnp/dense paths it is
    the same formula applied after the matmul."""
    eng = cfg.engine
    if "wg" in p:
        g = sl.apply(p["wg"], x, engine=eng, act="silu")
        h = g * sl.apply(p["wi"], x, engine=eng)
    else:
        h = sl.apply(p["wi"], x, engine=eng, act="gelu")
    return sl.apply(p["wo"], h, engine=eng)
