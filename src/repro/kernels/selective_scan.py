"""Pallas fused selective scan (Mamba-1) — §Perf iteration F4.

The XLA path (models/ssm.py) materializes every associative-scan log-stage
as a distinct [B, c, d_inner, N] HBM tensor; two measured attempts to cut
that traffic (bf16 elements, smaller chunks) were refuted (EXPERIMENTS.md
§Perf F1/F2) because the stage materialization itself is the cost.  This
kernel removes it structurally: the recurrence runs *inside* VMEM.

Layout: grid (B, d_inner/bd, S/c), sequence innermost so the state tile
``h [bd, N]`` lives in a VMEM scratch across sequence chunks of one
(batch, channel-tile) lane; per grid step the kernel loads
(dt, x) [c, bd] and (Bc, Cc) [c, N] tiles and runs the c-step recurrence
with a fori_loop:

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) outer B_t ;  y_t = h_t . C_t

HBM traffic per element: read dt, x, B, C + write y (+ state at chunk
boundaries) — no intermediate [.., c, d, N] tensors ever leave VMEM.
VMEM per step: (2c*bd + 2c*N + bd*N) * 4 B  ~= 0.6 MiB at c=128, bd=512,
N=16.  Matches the pure-jnp oracle (ref.selective_scan) to fp32 tolerance
in interpret mode (tests/test_kernels.py::test_selective_scan_kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(nc: int, dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref,
            y_ref, hout_ref, h_scratch):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_scratch[...] = h0_ref[0]                    # [bd, N]

    dt = dt_ref[0].astype(jnp.float32)                # [c, bd]
    xv = x_ref[0].astype(jnp.float32)                 # [c, bd]
    bv = b_ref[0].astype(jnp.float32)                 # [c, N]
    cv = c_ref[0].astype(jnp.float32)                 # [c, N]
    a = a_ref[...].astype(jnp.float32)                # [bd, N]
    c_len = dt.shape[0]

    def step(t, carry):
        h, ys = carry
        decay = jnp.exp(dt[t][:, None] * a)           # [bd, N]
        inp = (dt[t] * xv[t])[:, None] * bv[t][None, :]
        h = decay * h + inp
        y_t = jnp.sum(h * cv[t][None, :], axis=1)     # [bd]
        ys = jax.lax.dynamic_update_index_in_dim(ys, y_t, t, 0)
        return h, ys

    ys0 = jnp.zeros((c_len, dt.shape[1]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, c_len, step, (h_scratch[...], ys0))
    h_scratch[...] = h
    y_ref[0] = ys.astype(y_ref.dtype)

    @pl.when(s == nc - 1)
    def _finish():
        hout_ref[0] = h.astype(hout_ref.dtype)


def selective_scan(dt, x, bc, cc, a, h0, *, chunk: int = 128,
                   bd: int = 512, interpret: bool = False):
    """dt,x [B,S,di]; bc,cc [B,S,N]; a [di,N]; h0 [B,di,N].
    Returns (y [B,S,di], h_last [B,di,N])."""
    B, S, di = dt.shape
    N = bc.shape[-1]
    bd = min(bd, di)
    chunk = min(chunk, S)
    assert di % bd == 0 and S % chunk == 0
    grid = (B, di // bd, S // chunk)
    nc = S // chunk
    return pl.pallas_call(
        functools.partial(_kernel, nc),
        name="selective_scan",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b, d, s: (b, s, d)),   # dt
            pl.BlockSpec((1, chunk, bd), lambda b, d, s: (b, s, d)),   # x
            pl.BlockSpec((1, chunk, N), lambda b, d, s: (b, s, 0)),    # B
            pl.BlockSpec((1, chunk, N), lambda b, d, s: (b, s, 0)),    # C
            pl.BlockSpec((bd, N), lambda b, d, s: (d, 0)),             # A
            pl.BlockSpec((1, bd, N), lambda b, d, s: (b, d, 0)),       # h0
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b, d, s: (b, s, d)),   # y
            pl.BlockSpec((1, bd, N), lambda b, d, s: (b, d, 0)),       # h_last
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), dt.dtype),
            jax.ShapeDtypeStruct((B, di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret,
    )(dt, x, bc, cc, a, h0)


def hbm_bytes(B: int, S: int, di: int, N: int, elt: int = 4) -> int:
    """Analytic HBM traffic of the fused kernel (the §Perf F4 model)."""
    return elt * (2 * B * S * di          # dt, x reads
                  + 2 * B * S * N         # B, C reads
                  + B * S * di            # y write
                  + 2 * B * di * N)       # h0 read + h_last write
