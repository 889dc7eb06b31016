"""Percentiles and window arithmetic of the harness."""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics
    (numpy's default); NaN for no values."""
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, q)) if v.size else math.nan


def in_window(t: float, start: float, end: float) -> bool:
    """A moment belongs to the window [start, end)."""
    return start <= t < end


def latencies(records: list[dict], start: float, end: float,
              stop: float) -> list[float]:
    """Seconds from due to delivered, for every request due in the
    window: what a client of this non-streaming engine waits. The client
    serves on past the window's end until `stop`; a request not delivered
    by then counts at (stop - due), so a stall raises the numbers instead
    of shrinking the sample."""
    return [(r["done"] if r.get("done") is not None else stop) - r["due"]
            for r in records if in_window(r["due"], start, end)]


def first_token_waits(records: list[dict], start: float, end: float,
                      stop: float) -> list[float]:
    """Seconds from due to first token for every request due in the
    window. The first token's moment is the program's (submit +
    ttft_wall), known once the request is delivered; a request not
    delivered by `stop` counts at (stop - due)."""
    return [(r["submit"] + r["ttft_wall"] if r.get("done") is not None
             else stop) - r["due"]
            for r in records if in_window(r["due"], start, end)]


def per_token(records: list[dict], start: float, end: float) -> list[float]:
    """Seconds per token after the first, for requests due in the window
    and delivered: (delivered - first token) / (tokens - 1)."""
    return [(r["done"] - r["submit"] - r["ttft_wall"]) / (r["n"] - 1)
            for r in records
            if r.get("done") is not None and in_window(r["due"], start, end)
            and r["n"] > 1]
