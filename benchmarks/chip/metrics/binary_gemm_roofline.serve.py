"""Least time of the traced binary GEMMs over their device time, in %."""
from chipbench import readers


def read(ctx):
    return readers.roofline(ctx, "gemm")
