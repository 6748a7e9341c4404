"""Share of the roofline that the junction kernels reach in the train
step: the least time of the window's junction work (fwd, dx and dw
products at the configured density, work.py) at the chip's peaks, over
the device time of every junction_* kernel in the trace."""
from chipbench import readers


def read(ctx):
    return readers.roofline(ctx, "junction_", "junction_flops",
                            "junction_bytes")
