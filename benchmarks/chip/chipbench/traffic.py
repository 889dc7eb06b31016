"""One generator for every traffic mix: a mix file holds only parameters.

Every seed gets the same work. Lengths and gaps between arrivals are the
quantiles of their distributions at the points (i + 0.5) / n, one per
request, in an order drawn once from the mix's name; token ids come from
the seed. So two seeds differ in content, never in the amount or order of
work: a window holds a few tens of requests, and an order drawn from the
seed moved the median latency by a third from seed to seed (PERF.md).

A mix file (`traffic/<name>.json`) has:
  arrivals   {"kind": "poisson", "rate": req/s} or
             {"kind": "backlog", "depth_per_slot": d} (the queue waiting
             for admission is kept at d x slots requests)
  prompt     a length distribution, or, for shared system prompts,
  system     {"count", "lo", "hi", "zipf"}: `count` prompts with lengths
             spread over [lo, hi], picked with Zipf(zipf) popularity,
  user       the length distribution of the unique part that follows
  output     the length distribution of the tokens each request asks for
  warmup_s   seconds of the mix served before the window opens
A length distribution is {"dist": "uniform", "lo", "hi"} or
{"dist": "lognormal", "median", "sigma", "lo", "hi"} (clipped).
"""
from __future__ import annotations

import dataclasses
import math
import zlib

import numpy as np


@dataclasses.dataclass
class Req:
    prompt: np.ndarray        # int32 token ids
    max_new: int
    due: float                # seconds after the phase starts (0 for backlog)
    system: int = -1          # index of its shared system prompt, or -1


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """n lengths at the distribution's stratified quantiles, ascending."""
    u = _quantiles(n)
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if dist["dist"] == "uniform":
        x = lo + np.floor(u * (hi - lo + 1))
    elif dist["dist"] == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(x, lo, hi).astype(np.int64)


def poisson_dues(rate: float, n: int, span: float,
                 rng: np.random.Generator) -> np.ndarray:
    """n arrival times in [0, span): exponential gaps at stratified
    quantiles, shuffled, scaled so that the n gaps fill the span."""
    gaps = rng.permutation(-np.log1p(-_quantiles(n)) / rate)
    return (np.cumsum(gaps) - gaps) * (span / gaps.sum())


def zipf_picks(count: int, s: float, n: int) -> np.ndarray:
    """n picks among `count` items at Zipf(s) popularity, stratified."""
    p = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(p / p.sum())
    return np.minimum(np.searchsorted(cdf, _quantiles(n)), count - 1)


def system_prompts(mix: dict, vocab: int, seed: int) -> list[np.ndarray]:
    """The mix's shared system prompts, the same in warm-up and window."""
    sysd = mix.get("system")
    if not sysd:
        return []
    lens = lengths({"dist": "uniform", "lo": sysd["lo"], "hi": sysd["hi"]},
                   sysd["count"])
    # which popularity rank gets which length is fixed for every seed, so
    # every seed shares the same number of prompt tokens
    lens = np.random.default_rng(0).permutation(lens)
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]


def phase(mix: dict, vocab: int, seed: int, phase_id: int, span: float,
          n_backlog: int = 0) -> list[Req]:
    """The requests of one phase (0 = warm-up, 1 = window), in due order.
    A Poisson phase holds round(rate x span) requests due over the span;
    a backlog phase holds `n_backlog` requests, all due at once."""
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        n = max(1, round(arr["rate"] * span))
    elif arr["kind"] == "backlog":
        n = n_backlog
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    order = np.random.default_rng([zlib.crc32(mix["name"].encode()),
                                   phase_id])
    rng = np.random.default_rng([seed, 2, phase_id])
    outs = order.permutation(lengths(mix["output"], n))
    systems = system_prompts(mix, vocab, seed)
    if systems:
        picks = order.permutation(zipf_picks(len(systems),
                                             mix["system"]["zipf"], n))
        own = order.permutation(lengths(mix["user"], n))
    else:
        picks = np.full(n, -1)
        own = order.permutation(lengths(mix["prompt"], n))
    dues = (poisson_dues(arr["rate"], n, span, order)
            if arr["kind"] == "poisson" else np.zeros(n))
    reqs = []
    for i in range(n):
        tail = rng.integers(0, vocab, int(own[i])).astype(np.int32)
        prompt = (np.concatenate([systems[picks[i]], tail])
                  if picks[i] >= 0 else tail)
        reqs.append(Req(prompt, int(outs[i]), float(dues[i]), int(picks[i])))
    return reqs


def longest_request(mix: dict) -> int:
    """Most positions one request can reach (prompt + output)."""
    hi = mix["output"]["hi"]
    if mix.get("system"):
        return mix["system"]["hi"] + mix["user"]["hi"] + hi
    return mix["prompt"]["hi"] + hi


def check(mix: dict, max_len: int) -> None:
    """Refuse a mix on which an operation would fail: every request has
    to fit the engine's positions."""
    if longest_request(mix) > max_len:
        raise ValueError(f"mix {mix['name']}: a request can reach "
                         f"{longest_request(mix)} positions > max_len "
                         f"{max_len}")
    if not math.isfinite(mix.get("warmup_s", 0)):
        raise ValueError("warmup_s must be finite")
