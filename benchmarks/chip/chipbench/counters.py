"""The program's own counters (`Scheduler.stats`, `decode_steps()`), read
as changes between the snapshots at the window's edges.

A program that lacks a counter reads as None: the metric that needs it is
then left out of the result line, not made up.
"""
from __future__ import annotations


def delta(ctx: dict, key: str):
    """Change of counter `key` over the window: `steps` is the device's
    decode-step count, any other key one of `Scheduler.stats`."""
    a, b = ctx["snapshots"]["start"], ctx["snapshots"]["end"]
    if key == "steps":
        return b["steps"] - a["steps"]
    if key not in a["stats"]:
        return None
    return b["stats"][key] - a["stats"][key]


def ms_per(ctx: dict, calls: str, plus: tuple[str, ...],
           minus: tuple[str, ...] = ()):
    """Milliseconds per call over the window: the change of the second
    counters `plus` less that of `minus`, over the change of `calls`."""
    d = {k: delta(ctx, k) for k in (calls, *plus, *minus)}
    if None in d.values() or d[calls] <= 0:
        return None
    secs = sum(d[k] for k in plus) - sum(d[k] for k in minus)
    return 1e3 * secs / d[calls]
