"""Pallas LUT activation — the paper's BRAM sigmoid tables in VMEM.

The FPGA pre-computes sigma / sigma' for all 2^b_w codes (4096 entries at
b_w=12; Sec. III-D-1) and looks activations up instead of evaluating exp.
On TPU the 4096-entry fp32 table is 16 KiB — it sits in VMEM for the whole
kernel and every element of the tile gathers from it.  (DESIGN.md notes
that on TPU the VPU's native exp is competitive; this kernel exists for
bit-exact parity with the hardware and as the repro's activation path.)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(code_ref, table_ref, o_ref):
    codes = code_ref[...]
    o_ref[...] = jnp.take(table_ref[...], codes, axis=0)


def lut_lookup(codes, table, *, bm: int = 256, interpret: bool = False):
    """codes [M, N] int32 in [0, len(table)); table [T] f32 -> [M, N] f32.

    A ragged M pads to the row tile and slices back (padding code 0 just
    gathers table[0] into rows that are discarded)."""
    M, N = codes.shape
    T = table.shape[0]
    pm = (-M) % bm
    if pm:
        codes = jnp.pad(codes, ((0, pm), (0, 0)))
    Mp = M + pm
    grid = (Mp // bm,)
    out = pl.pallas_call(
        _kernel,
        name="sigmoid_lut",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, N), lambda m: (m, 0)),
            pl.BlockSpec((T,), lambda m: (0,)),   # whole table resident
        ],
        out_specs=pl.BlockSpec((bm, N), lambda m: (m, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), table.dtype),
        interpret=interpret,
    )(codes, table)
    return out[:M] if pm else out
