"""Share of the traced window in which no operation ran on the device, in %."""
from chipbench import readers


def read(ctx):
    return readers.idle(ctx)
