"""Model FLOP/s utilisation of the train step: model work of the window
(work.py; recomputation not counted) over the traced window, as a share
of the chip's bf16 peak."""
from chipbench import readers


def read(ctx):
    return readers.mfu(ctx, "model_flops")
