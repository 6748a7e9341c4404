"""Model FLOP/s utilisation of the expert-layer train step: model work of
the window (work_moe.py: routed-expert work on the rows routed to the
held experts; recomputation and padding not counted) over the traced
window, as a share of the chip's bf16 peak."""
from chipbench import readers


def read(ctx):
    return readers.mfu(ctx, "model_flops")
