"""Share of the traced device busy time spent outside the Pallas kernels
(packed attention, binary GEMMs), in %: the pool copies and update-slices,
norms, sampling and the other XLA ops around the kernels. Busy time is
the union of op intervals, so the share cannot pass 100%."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - sum(tr["kernel_s"].values()) / tr["busy_s"])
