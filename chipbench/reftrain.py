"""Plain Adam steps for the training references, in float32, with a
global-norm clip and the cosine warm-up schedule.

Parameters are stored, after every step, in the dtype the training job
states (a bfloat16 job keeps bfloat16 weights: each step computes in
float32 and rounds the result), as the job's own update does.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def cosine_lr(opt: dict, step: int) -> float:
    peak, warm, total = opt["lr"], opt["warmup"], opt["total"]
    if step < warm:
        return peak * step / max(1, warm)
    prog = min(max((step - warm) / max(1, total - warm), 0.0), 1.0)
    return 0.5 * peak * (1 + math.cos(math.pi * prog))


def store(t, dtype):
    """``t`` rounded to ``dtype`` (nearest, ties to even), kept as
    float32.  ``reduce_precision`` and not a round trip through
    ``astype``: the compiler may drop a convert pair as excess
    precision, and the rounding is the point here."""
    fi = jnp.finfo(dtype)
    if fi.bits >= 32:
        return t.astype(jnp.float32)
    return jax.lax.reduce_precision(t.astype(jnp.float32),
                                    exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def adam_steps(grad_fn, params, batches, opt: dict, param_dtype,
               start_step: int, on_first):
    """Run len(batches) Adam steps from ``params`` (float32 values that
    ``param_dtype`` can hold).  Returns (losses, ``on_first`` of the
    first gradient as Adam got it (after the clip), params after the
    last step)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    clip, wd = opt.get("grad_clip"), opt.get("weight_decay", 0.0)
    dt = jnp.dtype(param_dtype)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(params, m, v, g, lr, t):
        with jax.default_matmul_precision("highest"):
            scale = jnp.float32(1.0)
            if clip is not None:
                gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                  for x in jax.tree.leaves(g)))
                scale = jnp.minimum(1.0, clip / (gn + 1e-9))
            g = jax.tree.map(lambda x: x * scale, g)
            m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
            v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
            c1 = 1 - b1 ** t
            c2 = 1 - b2 ** t

            def step(p, a, s):
                u = (a / c1) / (jnp.sqrt(s / c2) + eps) + wd * p
                return store(p - lr * u, dt)
            return jax.tree.map(step, params, m, v), m, v, g

    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, tokens in enumerate(batches):
        step = start_step + i
        loss, g = grad_fn(params, tokens)
        losses.append(float(loss))
        params, m, v, g = update(params, m, v, g,
                                 jnp.float32(cosine_lr(opt, step)),
                                 jnp.float32(step + 1))
        if first is None:
            first = on_first(g)
        del g
    return losses, first, params
