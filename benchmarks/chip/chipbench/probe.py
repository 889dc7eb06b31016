"""What the scheduler has done, read at the edges of a window.

The program exposes per-request results only at completion and no count
of tokens generated, of chunks run or of first tokens given. To count the
work inside a window exactly, the harness reads the scheduler's own
bookkeeping at the window's two edges (between polls, when the device is
idle): which request holds which slot and how many tokens it has, which
admissions are mid-way and at which chunk. `window_work` turns two such
snapshots and the requests delivered between them into tokens decoded,
keys attended, chunks prefilled and rows occupied; the configuration's
block module (`blocks/<name>.py`, `work_of`) turns those into operations
and bytes.
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
    # what the totals sum, for layers that attend a window: each
    # request's decode (plen, first, last) and each chunk's (lo, hi)
    decodes: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)

    def keys(self, window: int = 0) -> tuple[int, int, int]:
        """(decode keys, chunk keys, chunk key reads) of a layer that
        attends at most `window` positions (0: every one); at 0 they are
        `decode_keys`, `chunk_keys` and `chunk_kv`."""
        return (sum(costs.decode_keys(*d, window) for d in self.decodes),
                sum(costs.chunk_keys(*c, window) for c in self.spans),
                sum(costs.chunk_reads(*c, window) for c in self.spans))


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
        w.decodes.append((plen, first, last))
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
            w.spans.append((lo, hi))
    return w

