"""Pipeline parallelism — the paper's junction pipelining at mesh scale.

The FPGA runs all L junctions simultaneously on different inputs with FF,
BP and UP overlapped (Fig. 1), updating weights with bounded staleness.
Generalized here to a "stage" mesh axis with shard_map + lax.ppermute:

* ``gpipe_step``  — synchronous microbatch pipeline (the baseline the paper
  implicitly beats): forward streams S+M-1 ticks, autodiff reverses it;
  bubble fraction = (S-1)/(M+S-1) in each direction.

* ``async_pipeline_epoch`` — the paper-faithful schedule: every tick, each
  stage does FF on one microbatch, BP on another, and UP with the gradient
  that just arrived — activations flow right, gradients flow left, weights
  update with staleness 2*(S - s) - 1 ticks, and there is NO bubble: one
  microbatch enters and one update lands per tick per stage (the "3L
  speedup" claim).  PipeDream-style semantics; convergence parity is
  validated in tests/test_pipeline.py.

Stages are homogeneous: ``stage_fn(stage_params, x) -> y`` with x/y of
identical shape; the last stage's output feeds ``loss_grad_fn(y, target)``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def _shift_right(x, axis_name):
    """stage s receives from s-1 (stage 0 receives zeros)."""
    n = jax.lax.axis_size(axis_name)
    perm = [(i, i + 1) for i in range(n - 1)]
    return jax.lax.ppermute(x, axis_name, perm)


def _shift_left(x, axis_name):
    n = jax.lax.axis_size(axis_name)
    perm = [(i + 1, i) for i in range(n - 1)]
    return jax.lax.ppermute(x, axis_name, perm)


# ===================================================================== GPipe
def gpipe_forward(stage_fn: Callable, params_stacked, x_microbatches,
                  mesh: Mesh, axis: str = "stage"):
    """Forward pipeline.  params_stacked: leading dim = n_stages;
    x_microbatches: [M, mb, ...].  Returns outputs [M, mb, ...]."""
    n_stages = mesh.shape[axis]

    def per_stage(params, xs):
        params = jax.tree.map(lambda t: t[0], params)   # my stage's slice
        M = xs.shape[0]
        sidx = jax.lax.axis_index(axis)
        T = M + n_stages - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            m_in = t - sidx                      # microbatch arriving here
            x_first = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), keepdims=False)
            x_in = jnp.where(sidx == 0, x_first, buf)
            y = stage_fn(params, x_in)
            valid = (m_in >= 0) & (m_in < M)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            # last stage writes its result
            outs = jax.lax.cond(
                valid & (sidx == n_stages - 1),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(m_in, 0, M - 1), 0),
                lambda o: o, outs)
            buf = _shift_right(y, axis)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(T))
        # only the last stage holds real outputs (zeros elsewhere) — psum
        # makes the P() out_spec correct on every device
        return jax.lax.psum(outs, axis)

    spec_p = jax.tree.map(lambda _: P(axis), params_stacked)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(spec_p, P()), out_specs=P(),
                       check_vma=False)
    return fn(params_stacked, x_microbatches)


def gpipe_loss(stage_fn, loss_fn, params_stacked, xs, ys, mesh, axis="stage"):
    outs = gpipe_forward(stage_fn, params_stacked, xs, mesh, axis)
    return loss_fn(outs, ys)


def gpipe_step(stage_fn, loss_fn, params_stacked, xs, ys, mesh, lr,
               axis="stage"):
    """One synchronous training step (grad through the pipeline)."""
    l, g = jax.value_and_grad(
        functools.partial(gpipe_loss, stage_fn, loss_fn))(
            params_stacked, xs, ys, mesh, axis)
    new = jax.tree.map(lambda p, gg: p - lr * gg, params_stacked, g)
    return new, l


# ============================================================== async (paper)
def async_pipeline_epoch(stage_fn: Callable, loss_grad_fn: Callable,
                         params_stacked, xs, ys, mesh: Mesh, lr: float,
                         axis: str = "stage"):
    """Paper-faithful asynchronous pipeline (FF/BP/UP overlapped, stale
    updates, zero bubble).

    Per tick, per stage s (all reads at tick start, writes at tick end):
      FF : x from stage s-1, stash it, send activation right
      BP : gradient from stage s+1, pop the matching stash, vjp -> (dparams, dx)
      UP : params -= lr * dparams      (staleness 2*(S-s)-1 ticks)
    """
    n_stages = mesh.shape[axis]

    def per_stage(params, xs, ys):
        params = jax.tree.map(lambda t: t[0], params)
        M = xs.shape[0]
        sidx = jax.lax.axis_index(axis)
        depth = 2 * n_stages          # stash ring depth (>= max staleness)
        stash = jnp.zeros((depth,) + xs.shape[1:], xs.dtype)
        act_buf = jnp.zeros_like(xs[0])     # activation arriving from left
        grad_buf = jnp.zeros_like(xs[0])    # gradient arriving from right
        T = M + 2 * n_stages
        losses = jnp.zeros((T,))

        def tick(carry, t):
            params, stash, act_buf, grad_buf, losses = carry
            # ---------------- FF on microbatch m_f = t - s
            m_f = t - sidx
            x_first = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(m_f, 0, M - 1), keepdims=False)
            x_in = jnp.where(sidx == 0, x_first, act_buf)
            ff_valid = (m_f >= 0) & (m_f < M)
            y = stage_fn(params, x_in)
            stash2 = jax.lax.dynamic_update_index_in_dim(
                stash, x_in, t % depth, 0)
            # last stage: loss gradient for m_f, starts flowing back
            y_t = jax.lax.dynamic_index_in_dim(
                ys, jnp.clip(m_f, 0, M - 1), keepdims=False)
            gy, l = loss_grad_fn(y, y_t)
            losses = jax.lax.dynamic_update_index_in_dim(
                losses, jnp.where(ff_valid & (sidx == n_stages - 1), l, 0.0),
                jnp.clip(t, 0, T - 1), 0)
            # ---------------- BP/UP on microbatch m_b = t - (2S - s - 2)
            m_b = t - (2 * n_stages - sidx - 2)
            bp_valid = (m_b >= 0) & (m_b < M)
            # stash slot where m_b's input was saved: tick t_f = m_b + s
            slot = (m_b + sidx) % depth
            x_saved = jax.lax.dynamic_index_in_dim(stash2, slot, keepdims=False)
            g_in = jnp.where(sidx == n_stages - 1,
                             jnp.where(ff_valid, gy, jnp.zeros_like(gy)),
                             grad_buf)
            _, vjp = jax.vjp(stage_fn, params, x_saved)
            dparams, dx = vjp(g_in)
            upd = jnp.where(bp_valid | (sidx == n_stages - 1), 1.0, 0.0)
            params = jax.tree.map(
                lambda p, g: p - lr * upd * g, params, dparams)
            # ---------------- communicate
            act_buf2 = _shift_right(jnp.where(ff_valid, y, jnp.zeros_like(y)),
                                    axis)
            grad_buf2 = _shift_left(dx, axis)
            return (params, stash2, act_buf2, grad_buf2, losses), None

        carry = (params, stash, act_buf, grad_buf, losses)
        (params, *_, losses), _ = jax.lax.scan(tick, carry, jnp.arange(T))
        return jax.tree.map(lambda t: t[None], params), losses

    spec_p = jax.tree.map(lambda _: P(axis), params_stacked)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(spec_p, P(), P()),
                       out_specs=(spec_p, P(axis)),
                       check_vma=False)
    new_params, losses = fn(params_stacked, xs, ys)
    return new_params, losses


def bubble_fraction(n_stages: int, n_microbatches: int,
                    schedule: str = "gpipe") -> float:
    """Idle fraction per stage — the paper's zero-bubble claim quantified."""
    if schedule == "gpipe":
        return 2.0 * (n_stages - 1) / (n_microbatches + 2.0 * (n_stages - 1))
    return 0.0  # async: every tick does useful FF+BP+UP once warm
