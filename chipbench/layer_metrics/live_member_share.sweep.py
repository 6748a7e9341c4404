"""Share of the member-steps the population kernels computed that
belonged to live members (a pruned member's slot is still computed,
masked, until its whole cohort is pruned): a count from the sweeps'
ledgers."""


def read(ctx):
    computed = ctx.counters.get("computed_member_steps")
    if not computed:
        return None
    return 100.0 * ctx.counters["live_member_steps"] / computed
