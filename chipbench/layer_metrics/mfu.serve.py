"""Share of the chip's peak that the serving window's work reaches: the
least time of the window's work (prefill chunks at peak bf16 FLOP/s,
decode ticks at peak HBM bandwidth: the weights once per tick and each
live slot's keys and values at its length; drivers/serve.py) over the
traced window."""


def read(ctx):
    c, s = ctx.counters, ctx.summary
    if s is None or s.window_s <= 0 or "prefill_flops" not in c:
        return None
    least = (c["prefill_flops"] / ctx.peaks["bf16_flops"]
             + c["decode_bytes"] / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / s.window_s
