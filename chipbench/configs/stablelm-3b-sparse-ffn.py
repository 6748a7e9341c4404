"""Plain reference of stablelm-3b (StableLmForCausalLM) with block-sparse
FFN junctions, in float32 jax.numpy: no kernels, no cache, no batching,
one sequence at a time.

Per layer (Hugging Face ``modeling_stablelm``, sequential residual, no
qkv bias):

    h = x + o_proj(attn(LN1(x)))
    y = h + down(silu(gate(LN2(h))) * up(LN2(h)))

with LayerNorm (scale and bias, eps from the file), causal softmax
attention over all heads scaled by 1/sqrt(head_dim), and rotary
embedding on the first ``partial_rotary_factor * head_dim`` dims of q and
k (rotate-half form, inverse frequencies theta^(-2i/rot)).  Then a final
LayerNorm and the untied unembedding; the loss is the mean cross-entropy
of every next token.

Departure from the published model: gate, up and down are the paper's
pre-defined sparse junctions.  A junction keeps, for each output block
``o``, the input blocks ``idx[o, :]``: ``y[:, o] = sum_t x[:, idx[o, t]]
@ w[o, t]`` (blocks of ``block`` features).

``sequence_logits`` gives every position's next-token logits, for the
check of served tokens.

``lowp`` rounds both operands of every matrix product (projections,
scores, p @ v, junctions, unembedding) to that float type with one scale
per tensor: the control that a lower precision than the configuration's
must fail.  The backward pass uses the same rounded operands.
"""
from __future__ import annotations

import functools

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


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def rotary(x, positions, rot, theta):
    """x [S, H, hd]: rotate the first ``rot`` dims (rotate-half form)."""
    half = rot // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) * 2 / rot)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def junction(x, w, idx, lowp):
    """x [S, n_in], w [nob, kb, bs, bs], idx [nob, kb] -> [S, nob * bs]."""
    nob, kb, bs, _ = w.shape
    xb = x.reshape(x.shape[0], -1, bs)[:, idx]          # [S, nob, kb, bs]
    y = _mm("sokb,okbc->soc", xb, w, lowp)
    return y.reshape(x.shape[0], nob * bs)


def final_hidden(conf, pats, params, tokens, lowp=None):
    """The final LayerNorm's output for one sequence ``tokens`` [S]."""
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    Hkv = conf["num_key_value_heads"]
    hd = d // H
    rot = int(hd * conf["partial_rotary_factor"])
    rot -= rot % 2
    eps = conf["layer_norm_eps"]
    theta = float(conf["rope_theta"])
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = params["embed/tok"][tokens]

    def layer(x, lp):
        h = layer_norm(x, lp["norm1/scale"], lp["norm1/bias"], eps)
        q = _mm("sd,df->sf", h, lp["attn/wq/w"], lowp).reshape(S, H, hd)
        k = _mm("sd,df->sf", h, lp["attn/wk/w"], lowp).reshape(S, Hkv, hd)
        v = _mm("sd,df->sf", h, lp["attn/wv/w"], lowp).reshape(S, Hkv, hd)
        q, k = rotary(q, pos, rot, theta), rotary(k, pos, rot, theta)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = _mm("qhd,khd->hqk", q, k, lowp) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        a = _mm("hqk,khd->qhd", p, v, lowp).reshape(S, H * hd)
        x = x + _mm("sf,fd->sd", a, lp["attn/wo/w"], lowp)
        h = layer_norm(x, lp["norm2/scale"], lp["norm2/bias"], eps)
        g = junction(h, lp["mlp/wg/w"], pats["wg"]["idx"], lowp)
        u = junction(h, lp["mlp/wi/w"], pats["wi"]["idx"], lowp)
        x = x + junction(jax.nn.silu(g) * u, lp["mlp/wo/w"],
                         pats["wo"]["idx"], lowp)
        return x, None

    layers = {k[len("layers/"):]: v for k, v in params.items()
              if k.startswith("layers/")}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, layers)
    return layer_norm(x, params["final_norm/scale"],
                      params["final_norm/bias"], eps)


def sequence_logits(conf, pats, params, tokens, lowp=None):
    """Next-token logits at every position of ``tokens`` [S]: [S, V]."""
    x = final_hidden(conf, pats, params, tokens, lowp)
    return _mm("sd,dv->sv", x, params["embed/out"], lowp)


def sequence_loss(conf, pats, params, tokens, lowp=None):
    """Summed next-token cross-entropy of one sequence ``tokens`` [S]."""
    x = final_hidden(conf, pats, params, tokens, lowp)
    logits = _mm("sd,dv->sv", x[:-1], params["embed/out"], lowp)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(lse - picked)


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


def make_served_gaps(conf, pats, seq_len, max_served, lowp=None):
    """gaps(params, prompt [P], served [N]) -> [N]: at each served
    token, how far its logit lies below the best logit of the reference
    (0 where the served token is the reference's greedy choice).  With
    ``lowp``, the token read is the one the lower precision puts first,
    and its gap is taken in the float32 logits (the control)."""
    pats = {k: {"idx": jnp.asarray(v["idx"])} for k, v in pats.items()}

    @jax.jit
    def gaps(params, tokens, served, start):
        with jax.default_matmul_precision("highest"):
            lg = sequence_logits(conf, pats, params, tokens)
            if lowp is not None:
                low = sequence_logits(conf, pats, params, tokens, lowp)
        rows = jax.lax.dynamic_slice_in_dim(lg, start, served.shape[0])
        if lowp is not None:
            lrows = jax.lax.dynamic_slice_in_dim(low, start, served.shape[0])
            served = jnp.argmax(lrows, axis=-1)
        picked = jnp.take_along_axis(rows, served[:, None], axis=-1)[:, 0]
        return jnp.max(rows, axis=-1) - picked

    def run(params, prompt, served):
        # one shape for every request: the causal mask keeps the padding
        # out of every position that is read
        n = len(served)
        tokens = np.zeros(seq_len, np.int32)
        tokens[:len(prompt) + n - 1] = np.concatenate([prompt, served[:-1]])
        pad = np.zeros(max_served, np.int32)
        pad[:n] = served
        out = gaps(params, jnp.asarray(tokens), jnp.asarray(pad),
                   len(prompt) - 1)
        return np.asarray(out)[:n]

    return run
