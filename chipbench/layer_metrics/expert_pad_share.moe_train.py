"""Share of the rows the expert kernels compute that no token was routed
to: 100 * (moe_computed_rows - moe_routed_rows) / moe_computed_rows over
the traced window, from the program's counters.  A program without the
counters gives nothing."""


def read(ctx):
    computed = ctx.counters.get("moe_computed_rows")
    routed = ctx.counters.get("moe_routed_rows")
    if not computed or routed is None:
        return None
    return 100.0 * (computed - routed) / computed
