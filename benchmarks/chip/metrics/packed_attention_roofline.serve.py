"""Least time of the traced packed attention over its device time, in %."""
from chipbench import readers


def read(ctx):
    return readers.roofline(ctx, "attn")
