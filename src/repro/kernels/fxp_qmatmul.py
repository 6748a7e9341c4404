"""Pallas fixed-point matmul with saturating post-accumulation clip.

The paper's arithmetic units keep one bit triplet (b_w, b_n, b_f) end to
end by clipping adder/multiplier outputs (Sec. III-C-3).  The TPU-native
re-expression: operands are integer *codes* (value * 2^b_f), products
accumulate exactly in int32 (codes fit 16 bits, so a 128-deep dot is
exact), then one round-half-up shift by b_f and a saturate to the triplet
range.  This is what an int8/int16 MXU path does on real hardware — the
FPGA's per-node clipping tree is kept bit-exact in core/fixed_point.py and
the two are compared in benchmarks/paper_benches.py (the Table II
bit-width rows, ``table2_bitwidth``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(bf: int, bn: int, nk: int, a_ref, w_ref, o_ref, acc_ref):
    # signature: inputs..., outputs..., scratch...
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _finish():
        acc = acc_ref[...]
        rounded = (acc + (1 << (bf - 1))) >> bf
        lo, hi = -(1 << (bn + bf)), (1 << (bn + bf)) - 1
        o_ref[...] = jnp.clip(rounded, lo, hi).astype(jnp.int32)


def qmatmul(a_code, w_code, *, bf: int, bn: int, bm: int = 128,
            bn_tile: int = 128, bk: int = 128, interpret: bool = False):
    """a [M, K] int32 codes, w [K, N] int32 codes -> [M, N] int32 codes.

    Ragged shapes pad to the tile and slice back (zero codes contribute
    exact zeros to the integer accumulation, so padding is free)."""
    M, K = a_code.shape
    N = w_code.shape[1]
    pm, pk, pn = (-M) % bm, (-K) % bk, (-N) % bn_tile
    if pm or pk:
        a_code = jnp.pad(a_code, ((0, pm), (0, pk)))
    if pk or pn:
        w_code = jnp.pad(w_code, ((0, pk), (0, pn)))
    Mp, Kp = a_code.shape
    Np = w_code.shape[1]
    grid = (Mp // bm, Np // bn_tile, Kp // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, bf, bn, Kp // bk),
        name="fxp_qmatmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn_tile), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn_tile), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn_tile), jnp.int32)],
        interpret=interpret,
    )(a_code, w_code)
    return out[:M, :N] if (pm or pn) else out
