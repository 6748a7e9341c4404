"""Share of the roofline that the routed experts' kernels reach: the
least time of the held experts' fwd, dx and weight-update work on the
rows routed to them (work_moe.py) at the chip's peaks, over the device
time of every expert_junction_* kernel in the trace (the padding of each
expert's last row tile is in the time, not in the work)."""
from chipbench import readers


def read(ctx):
    return readers.roofline(ctx, "expert_junction_", "expert_flops",
                            "expert_bytes")
