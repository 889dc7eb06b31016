"""p90 of ms per token after the first, (delivered - first token) / (tokens - 1), over requests due in the window and delivered."""
from chipbench import readers


def read(ctx):
    return readers.p90(ctx["per_token"], 1e3)
