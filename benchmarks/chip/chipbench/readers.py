"""Shared arithmetic of the per-layer metric readers in `metrics/`.

A reader returns None when it finds nothing to read; the harness then
leaves the metric out of the result line. A share of a roofline or of a
peak is never made up: no device time, no number.
"""
from __future__ import annotations

from chipbench import costs, stats


def p90(values, scale: float = 1.0):
    values = list(values)
    return scale * stats.percentile(values, 90) if values else None


def mfu(ctx: dict):
    """Model operations of the window's work (GEMMs, attention, head of
    every token prefilled and decoded) over window x int8 peak, in %."""
    if ctx["peak"] is None or ctx["work"].steps + ctx["work"].chunks == 0:
        return None
    ops = sum(w.ops for w in ctx["work_cost"].values())
    return 100.0 * ops / (ctx["window_s"] * ctx["peak"]["int8_ops_per_s"])


def roofline(ctx: dict, kind: str):
    """Least time of the traced window's `kind` work ('gemm' or 'attn')
    over the device time of those kernels in the trace, in %. The least
    time is the larger of ops at the int8 peak and bytes at the memory
    bandwidth, summed over the window (a lower bound of the per-call sum,
    so the share is never overstated)."""
    tr = ctx.get("trace")
    if tr is None or ctx["peak"] is None:
        return None
    dev_s = tr["kernel_s"].get(kind, 0.0)
    work = ctx["trace_work"][kind]
    if dev_s <= 0 or work.ops <= 0:
        return None
    t, bound = costs.least_time(work, ctx["peak"])
    ctx.setdefault("bounds", {})[kind] = bound
    return 100.0 * t / dev_s


def idle(ctx: dict):
    tr = ctx.get("trace")
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
