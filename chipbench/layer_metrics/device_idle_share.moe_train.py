"""Share of the traced window in which no operation ran on the chip."""
from chipbench import readers


def read(ctx):
    return readers.idle_share(ctx)
