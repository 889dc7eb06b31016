"""p90 of ms by which the client submitted a request after it was due (the client was inside a poll)."""
from chipbench import readers


def read(ctx):
    return readers.p90(ctx["submit_lag"], 1e3)
