"""ms per decode step over the window: the scheduler's device-synced burst
seconds (`decode_s`, timed by its `serve.burst` span) over its decode
steps. Each step advances every busy slot by one token; interleaved
prefill chunks are not in it, as they are in `tpot_p90_ms`."""
from chipbench import counters


def read(ctx):
    return counters.ms_per(ctx, "steps", ("decode_s",))
