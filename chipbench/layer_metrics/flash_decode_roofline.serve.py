"""Share of the HBM roofline that flash_decode reaches: the keys and
values the window's decode ticks must read (every live slot's cache at
its length, every layer) at peak bandwidth, over the device time of all
flash_decode kernels in the trace."""


def read(ctx):
    s = ctx.summary
    if s is None:
        return None
    t = s.kernel_s("flash_decode") / s.chips
    kv = ctx.counters.get("kv_bytes")
    if t <= 0 or not kv:
        return None
    return 100.0 * kv / ctx.peaks["hbm_bytes_per_s"] / t
