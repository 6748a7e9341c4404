"""Arithmetic the per-layer metric readers share.  Each reader returns
None where its run has nothing to read (no such kernel in the trace, no
such counter), and the harness then leaves the metric out."""
from __future__ import annotations

from chipbench import peaks as peaks_mod


def mfu(ctx, flops_key: str):
    flops = ctx.counters.get(flops_key)
    if not flops or ctx.summary is None or ctx.summary.window_s <= 0:
        return None
    return 100.0 * flops / ctx.summary.window_s / ctx.peaks["bf16_flops"]


def roofline(ctx, prefix: str, flops_key: str, bytes_key: str):
    if ctx.summary is None:
        return None
    t = ctx.summary.kernel_s(prefix) / ctx.summary.chips
    flops, bytes_ = ctx.counters.get(flops_key), ctx.counters.get(bytes_key)
    if t <= 0 or not flops:
        return None
    least, _ = peaks_mod.least_time_s(flops, bytes_, ctx.peaks)
    return 100.0 * least / t


def idle_share(ctx):
    if ctx.summary is None or ctx.summary.window_s <= 0:
        return None
    return 100.0 * ctx.summary.idle_share
