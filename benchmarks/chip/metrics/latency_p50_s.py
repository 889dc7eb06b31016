"""Median seconds from due to delivered, over every request due in the
window (one not delivered by the end of serving counts at that end): what
a client of this non-streaming engine waits. A median, since a window
holds a few tens of requests (no tail has ten beyond it)."""
from chipbench import stats


def read(ctx):
    return stats.percentile(ctx["latency"], 50) if ctx["latency"] else None
