"""What the scheduler has done, read at the edges of a window.

The program exposes per-request results only at completion and no count
of tokens generated, of chunks run or of first tokens given. To count the
work inside a window exactly, the harness reads the scheduler's own
bookkeeping at the window's two edges (between polls, when the device is
idle): which request holds which slot and how many tokens it has, which
admissions are mid-way and at which chunk. `window_work` turns two such snapshots and
the requests delivered between them into tokens decoded, keys attended,
chunks prefilled and rows occupied.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from chipbench import costs


def snapshot(sched, clock: float) -> dict:
    out_len = np.asarray(jax.device_get(sched._state["out_len"]))
    running = {info.rid: int(out_len[slot])
               for slot, info in sched._running.items()}
    admitting = {a.rid: (a.next, a.start) for a in sched._admitting}
    return {"t": clock, "steps": sched.decode_steps(),
            "stats": dict(sched.stats), "running": running,
            "admitting": admitting,
            "cached": dict(sched._cached_tokens)}


@dataclasses.dataclass
class WindowWork:
    steps: int = 0               # decode steps (all slots advance together)
    decoded: int = 0             # tokens produced by decode steps
    decode_keys: int = 0         # sum over those tokens of keys attended
    chunks: int = 0              # prefill chunk calls
    chunk_rows: int = 0          # valid prompt tokens in those chunks
    chunk_keys: int = 0          # sum over chunk queries of keys attended
    chunk_kv: int = 0            # key positions each chunk reads, summed
    firsts: int = 0              # first tokens (one per finished admission)


def window_work(a: dict, b: dict, reqs: dict, delivered: set,
                chunk: int) -> WindowWork:
    """Work between snapshots `a` and `b`. `reqs` maps rid -> (prompt
    length, tokens asked); `delivered` holds the rids delivered between
    the two snapshots (their cached prefix came with the completion)."""
    w = WindowWork(steps=b["steps"] - a["steps"])
    started = set(a["running"])
    for rid in set(b["running"]) | delivered:
        plen, n = reqs[rid]["plen"], reqs[rid]["n"]
        first = a["running"].get(rid, 1)
        last = b["running"].get(rid, n)
        w.decoded += max(0, last - first)
        w.decode_keys += costs.decode_keys(plen, first, last)
        if rid not in started:
            w.firsts += 1
    for rid in set(b["running"]) | delivered | set(b["admitting"]):
        if rid in started:
            continue                       # admitted before the window
        plen = reqs[rid]["plen"]
        cached = (b["admitting"][rid][1] if rid in b["admitting"]
                  else reqs[rid].get("cached", b["cached"].get(rid, 0)))
        n_chunks = max(1, -(-(plen - cached) // chunk))
        c0 = a["admitting"].get(rid, (0, cached))[0]
        c1 = b["admitting"].get(rid, (n_chunks, cached))[0]
        for c in range(c0, c1):
            lo = cached + c * chunk
            hi = min(plen, lo + chunk)
            w.chunks += 1
            w.chunk_rows += hi - lo
            w.chunk_keys += costs.chunk_keys(lo, hi)
            w.chunk_kv += hi
    return w


def work_of(m: dict, w: WindowWork) -> dict[str, costs.Work]:
    """The window's GEMM, attention and head work by the cost functions."""
    gemm = costs.block_gemms(m, w.decoded, w.steps) + \
        costs.block_gemms(m, w.chunk_rows, w.chunks)
    attn = costs.attention(m, w.decode_keys, w.decoded, w.decode_keys) + \
        costs.attention(m, w.chunk_keys, w.chunk_rows, w.chunk_kv)
    head = costs.head(m, w.decoded, w.steps) + costs.head(m, w.firsts,
                                                          w.firsts)
    return {"gemm": gemm, "attn": attn, "head": head}
