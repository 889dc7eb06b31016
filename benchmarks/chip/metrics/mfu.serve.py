"""Model operations of the window's work over window x int8 peak, in %."""
from chipbench import readers


def read(ctx):
    return readers.mfu(ctx)
