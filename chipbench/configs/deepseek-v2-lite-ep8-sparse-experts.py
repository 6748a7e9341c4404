"""Plain reference of DeepSeek-V2-Lite (DeepseekV2ForCausalLM) cut to one
chip's share of an 8-way expert-parallel layer, with block-sparse FFN
junctions, in float32 jax.numpy: no kernels, no cache, no batching, one
sequence at a time.  It imports nothing of the program.

Per layer (Hugging Face ``modeling_deepseek``, no q-LoRA):

    h = x + o_proj(MLA(RMSNorm1(x)))
    y = h + FFN(RMSNorm2(h))

MLA, expanded form: q = x Wq split per head into q_nope (128) and q_pe
(64); [c_kv, k_pe] = x Wkv_a, c_kv RMS-normalised (kv_a_layernorm) and
expanded by Wkv_b into k_nope (128) and v (128) per head; q_pe and k_pe
(one head, shared) are rotated with YaRN's frequencies; scores
(q_nope.k_nope + q_pe.k_pe) times mscale(40, mscale_all_dim)^2 /
sqrt(192), causal softmax, weighted sum of v, output projection.  YaRN
(``DeepseekV2YarnRotaryEmbedding``): inverse frequencies theta^(-2i/64),
divided by ``factor`` above the correction range and ramped linearly
inside it; the range runs from floor(d(beta_fast)) to ceil(d(beta_slow))
with d(r) = 64 ln(4096 / (2 pi r)) / (2 ln theta); the cos/sin factor
mscale(mscale) / mscale(mscale_all_dim) is 1 here.

FFN: layer 0 (``first_k_dense_replace``) a dense SwiGLU of width 10944;
the others an expert layer.  The router scores every token over all
``router_experts`` outputs (softmax of x W_gate in float32), keeps the
greedy top-k, renormalises them only if ``norm_topk_prob`` and scales by
``routed_scaling_factor``.  Each held expert (``n_routed_experts`` from
``first_held_expert``) is a SwiGLU computed for every token routed to
it, with no capacity; what experts held on other chips add is left out,
as the deployment leaves it to them.  The shared experts (one SwiGLU of
width ``n_shared_experts * moe_intermediate_size``) see every token.
The balance loss is seq_aux: per sequence, alpha * sum_i f_i P_i with
f_i = (E / (S k)) * (slots routed to expert i) and P_i the mean of its
probability, over all E router outputs.  Then a final RMSNorm and the
untied head over the held vocabulary slice; the loss is the mean
cross-entropy of every next token plus the balance loss.

Departures from the published model, each written down:

- gate, up and down of the routed and the shared experts are the
  paper's pre-defined sparse junctions: ``y[:, o] = sum_t x[:, idx[o,
  t]] @ w[o, t]`` (blocks of ``block``); layer 0's FFN and attention
  stay dense;
- rotary uses the rotate-half layout, not HF's interleaved one (a fixed
  permutation of Wq's and Wkv_a's rotary columns: with random weights
  no departure);
- depth, held experts and vocabulary are cut as the configuration file
  states.

``lowp`` rounds both operands of every matrix product to that float type
with one scale per tensor: the control that a lower precision than the
configuration's must fail.  The backward uses the same rounded operands.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _round(t, dtype):
    if dtype is None:
        return t
    if jnp.dtype(dtype) == jnp.bfloat16:
        q = jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)
    else:
        top = float(jnp.finfo(dtype).max)
        s = jax.lax.stop_gradient(jnp.max(jnp.abs(t)) / top + 1e-30)
        q = (t / s).astype(dtype).astype(jnp.float32) * s
    return t + jax.lax.stop_gradient(q - t)


def _mm(eq, a, b, lowp):
    return jnp.einsum(eq, _round(a, lowp), _round(b, lowp))


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(conf) -> np.ndarray:
    rs, dim = conf["rope_scaling"], conf["qk_rope_head_dim"]
    base = float(conf["rope_theta"])
    L = rs["original_max_position_embeddings"]

    def d(r):
        return dim * math.log(L / (r * 2 * math.pi)) / (2 * math.log(base))
    lo = max(math.floor(d(rs["beta_fast"])), 0)
    hi = min(math.ceil(d(rs["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / rs["factor"]
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(conf) -> float:
    rs = conf["rope_scaling"]
    scale = 1.0 / math.sqrt(conf["qk_nope_head_dim"]
                            + conf["qk_rope_head_dim"])
    assert _mscale(rs["factor"], rs["mscale"]) == _mscale(
        rs["factor"], rs["mscale_all_dim"])
    m = _mscale(rs["factor"], rs["mscale_all_dim"])
    return scale * m * m


def rotary(x, positions, inv):
    """x [S, H, d]: rotate-half form, pair (i, i + d/2) at inv[i]."""
    half = x.shape[-1] // 2
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def junction(x, w, idx, lowp):
    """x [S, n_in], w [nob, kb, bs, bs], idx [nob, kb] -> [S, nob * bs]."""
    nob, kb, bs, _ = w.shape
    xb = x.reshape(x.shape[0], -1, bs)[:, idx]          # [S, nob, kb, bs]
    y = _mm("sokb,okbc->soc", xb, w, lowp)
    return y.reshape(x.shape[0], nob * bs)


def mla(conf, lp, x, lowp, block=1024):
    S = x.shape[0]
    H = conf["num_attention_heads"]
    nope, rd = conf["qk_nope_head_dim"], conf["qk_rope_head_dim"]
    vd, lora = conf["v_head_dim"], conf["kv_lora_rank"]
    eps = conf["rms_norm_eps"]
    inv = jnp.asarray(yarn_inv_freq(conf))
    pos = jnp.arange(S)
    q = _mm("sd,df->sf", x, lp["attn/wq/w"], lowp).reshape(S, H, nope + rd)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], pos, inv)], -1)
    a = _mm("sd,df->sf", x, lp["attn/wkv_a/w"], lowp)
    lat = rms_norm(a[:, :lora], lp["attn/kv_norm/scale"], eps)
    k_pe = rotary(a[:, None, lora:], pos, inv)             # [S, 1, rd]
    kv = _mm("sl,lf->sf", lat, lp["attn/wkv_b/w"], lowp).reshape(
        S, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (S, H, rd))], -1)
    v = kv[..., nope:]
    scale = softmax_scale(conf)

    @jax.checkpoint
    def rows(qb, kb, vb, lo):
        s = _mm("qhd,khd->hqk", qb, kb, lowp) * scale
        qi = lo + jnp.arange(qb.shape[0])
        causal = qi[:, None] >= jnp.arange(kb.shape[0])[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", p, vb, lowp)

    block = min(block, S)
    outs = [rows(q[lo:lo + block], k[:lo + block], v[:lo + block], lo)
            for lo in range(0, S, block)]
    o = jnp.concatenate(outs, 0).reshape(S, H * vd)
    return _mm("sf,fd->sd", o, lp["attn/wo/w"], lowp)


def dense_ffn(lp, x, lowp):
    g = _mm("sd,df->sf", x, lp["mlp/wg/w"], lowp)
    u = _mm("sd,df->sf", x, lp["mlp/wi/w"], lowp)
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, lp["mlp/wo/w"], lowp)


def route(conf, router, x, lowp):
    """(probs [S, E], top-k weights [S, k], top-k experts [S, k])."""
    probs = jax.nn.softmax(_mm("sd,de->se", x, router, lowp), axis=-1)
    w, e = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    if conf["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return probs, w * conf["routed_scaling_factor"], e


def moe(conf, pats, lp, x, lowp):
    """(output, seq_aux) of one expert layer for one sequence."""
    S = x.shape[0]
    E, K = conf["router_experts"], conf["num_experts_per_tok"]
    probs, w, e = route(conf, lp["moe/router"], x, lowp)
    pin, pout = pats["expert_in"]["idx"], pats["expert_out"]["idx"]
    y = jnp.zeros_like(x)
    for j in range(conf["n_routed_experts"]):
        weight = jnp.sum(jnp.where(e == conf["first_held_expert"] + j, w, 0.0),
                         axis=-1)
        g = junction(x, lp["moe/wg"][j], pin, lowp)
        u = junction(x, lp["moe/wi"][j], pin, lowp)
        y = y + weight[:, None] * junction(jax.nn.silu(g) * u,
                                           lp["moe/wo"][j], pout, lowp)
    g = junction(x, lp["moe/shared/wg/w"], pats["shared_wg"]["idx"], lowp)
    u = junction(x, lp["moe/shared/wi/w"], pats["shared_wi"]["idx"], lowp)
    y = y + junction(jax.nn.silu(g) * u, lp["moe/shared/wo/w"],
                     pats["shared_wo"]["idx"], lowp)
    hits = jnp.zeros(E).at[e.reshape(-1)].add(1.0)
    f = hits * E / (S * K)
    aux = conf["aux_alpha"] * jnp.sum(f * jnp.mean(probs, axis=0))
    return y, aux


def _layers(params, prefix):
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def final_hidden(conf, pats, params, tokens, lowp=None):
    """(final RMSNorm's output [S, d], summed seq_aux) for one sequence."""
    eps = conf["rms_norm_eps"]
    x = params["embed/tok"][tokens]

    def block(x, lp, ffn):
        x = x + mla(conf, lp, rms_norm(x, lp["norm1/scale"], eps), lowp)
        y, aux = ffn(lp, rms_norm(x, lp["norm2/scale"], eps))
        return x + y, aux

    dense = _layers(params, "dense_layers/")
    for i in range(conf["first_k_dense_replace"]):
        lp = {k: v[i] for k, v in dense.items()}
        x, _ = jax.checkpoint(lambda x, lp: block(
            x, lp, lambda lp, h: (dense_ffn(lp, h, lowp), 0.0)))(x, lp)

    def layer(x, lp):
        return block(x, lp, lambda lp, h: moe(conf, pats, lp, h, lowp))

    x, aux = jax.lax.scan(jax.checkpoint(layer), x,
                          _layers(params, "layers/"))
    return rms_norm(x, params["final_norm/scale"], eps), jnp.sum(aux)


def sequence_loss(conf, pats, params, tokens, lowp=None):
    """Summed next-token cross-entropy of one sequence, plus its balance
    loss weighted by the number of predicted tokens (so the batch mean
    is the mean cross-entropy plus the mean balance loss)."""
    x, aux = final_hidden(conf, pats, params, tokens, lowp)
    logits = _mm("sd,dv->sv", x[:-1], params["embed/out"], lowp)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked) + aux * (tokens.shape[0] - 1)


def sequence_routes(conf, pats, params, tokens):
    """Top-k experts of every token at every expert layer [L, S, k], the
    reference's routing of one sequence (for the routing-agreement
    reading)."""
    eps = conf["rms_norm_eps"]
    x = params["embed/tok"][tokens]
    dense = _layers(params, "dense_layers/")
    for i in range(conf["first_k_dense_replace"]):
        lp = {k: v[i] for k, v in dense.items()}
        x = x + mla(conf, lp, rms_norm(x, lp["norm1/scale"], eps), None)
        x = x + dense_ffn(lp, rms_norm(x, lp["norm2/scale"], eps), None)

    def layer(x, lp):
        x = x + mla(conf, lp, rms_norm(x, lp["norm1/scale"], eps), None)
        h = rms_norm(x, lp["norm2/scale"], eps)
        _, _, e = route(conf, lp["moe/router"], h, None)
        y, _ = moe(conf, pats, lp, h, None)
        return x + y, e

    _, routes = jax.lax.scan(layer, x, _layers(params, "layers/"))
    return routes


def make_batch_grad(conf, pats, lowp=None):
    """grad(params, tokens [B, S]) -> (mean loss, mean gradients), one
    sequence at a time so that activations of one row are live at once."""
    pats = {k: {"idx": jnp.asarray(v["idx"])} for k, v in pats.items()}

    @jax.jit
    def row(params, tokens):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                functools.partial(sequence_loss, conf, pats, lowp=lowp),
                argnums=0)(params, tokens)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(acc, g):
        return jax.tree.map(jnp.add, acc, g)

    def grad(params, tokens):
        total, acc = None, None
        for b in range(tokens.shape[0]):
            loss, g = row(params, jnp.asarray(tokens[b]))
            total = loss if total is None else total + loss
            acc = g if acc is None else add(acc, g)
        n = tokens.shape[0] * (tokens.shape[1] - 1)
        return total / n, jax.tree.map(lambda t: t / n, acc)

    return grad


def make_routes(conf, pats):
    """routes(params, tokens [S]) -> [L, S, k] at the highest precision."""
    pats = {k: {"idx": jnp.asarray(v["idx"])} for k, v in pats.items()}

    @jax.jit
    def routes(params, tokens):
        with jax.default_matmul_precision("highest"):
            return sequence_routes(conf, pats, params, tokens)
    return routes
