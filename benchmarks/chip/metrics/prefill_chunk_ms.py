"""ms per prefill call over the window: the scheduler's device-synced
prefill seconds (`prefill_s`, timed by its `serve.chunk` span) over its
prefill calls (`chunks`): what one admission chunk holds the chip."""
from chipbench import counters


def read(ctx):
    return counters.ms_per(ctx, "chunks", ("prefill_s",))
