"""Shape-keyed autotuner + route cache for the packed kernels.

Every packed kernel entry point (`dispatch_binary_gemm{,_fused}`,
`decode_attention_packed`, `prefill_attention_packed`) asks this module
which realization to run for its static shape:

    route, params = tune.get_route("binary_gemm", m=m, n=n, kw=kw, pl=1)

Shapes are bucketed (size-like dims rounded up to powers of two; small
structural dims — kv heads, GQA group, head_dim, and the GEMMs' lhs
form `pl` (1 = packed uint32 lhs, 0 = float chain-entry lhs, which runs
a different kernel: in-kernel sign-pack over (bm, bk*32) float blocks)
— kept exact) and looked up in a per-backend JSON cache committed to
the repo
(`kernels/tuned/<backend>.json`), so CI hosts and fresh checkouts get
tuned routes without ever running the tuner. On a cache miss the answer
falls back to a backend heuristic — or, when `REPRO_AUTOTUNE=1` is set
and we are not inside a jax trace, the missing bucket is tuned on the
spot and persisted.

Tuning a bucket means: synthesize operands at the bucket shape, and for
every candidate in the route/block lattice (a) assert it is *bit-exact*
against the `ref.py` oracle — a candidate that changes any bit is
discarded loudly, never timed — then (b) time it jitted, and persist the
winner together with roofline metadata (flops, HBM bytes, arithmetic
intensity from `repro.roofline.hlo.analyze` of the winner's compiled
HLO), so `--show` can report where each tuned kernel sits against its
bytes/flops bound.

Route vocabulary (see kernels/binary_gemm.py for semantics):
    binary_gemm / binary_gemm_fused:  vpu | mxu | xla | float
    decode_attention / prefill_attention (and their _paged twins, which
    walk a page table over a shared pool):  pallas | xla

Why 'xla' exists: the oracle *is* a packed-arithmetic formulation; on
hosts where Pallas kernels run in interpret mode (CPU CI), letting XLA
compile the popcount expression is the fast packed path, and on TPU it is
the baseline the Pallas kernels must beat. Dispatch never changes
results — every route is bit-exact — so the cache is pure performance
metadata.

CLI:
    python -m repro.kernels.tune --tune [--force]   # tune standard shapes
    python -m repro.kernels.tune --check            # CI: cache complete?
    python -m repro.kernels.tune --show             # print decision table
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import time
from pathlib import Path
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

TUNED_DIR = Path(__file__).resolve().parent / "tuned"

# Size-like dims get pow2-bucketed; everything else is structural and kept
# exact in the key (a GQA group, head_dim or page size changes the
# kernel's inner shape, not just its extent). The paged attention pool
# size `p` is size-like; the page size `ps` is structural.
_BUCKETED = {"m", "n", "kw", "b", "t", "s", "p"}

# Candidate block lattices. Kept deliberately small: every entry is also a
# property-test case (tests must hold bit-exactness for anything the tuner
# may pick), so growing these grows CI time too.
GEMM_TILES = [
    dict(bm=128, bn=128, bk=8, uk=1),     # seed default: word-at-a-time
    dict(bm=128, bn=128, bk=32, uk=8),    # deeper K stream, 8-word slivers
    dict(bm=128, bn=256, bk=32, uk=0),    # wide N, whole-tile broadcast
    dict(bm=8, bn=256, bk=64, uk=0),      # decode-M tiles (tiny batch)
    dict(bm=256, bn=128, bk=16, uk=4),
]
FUSED_TILES = [
    dict(bm=128, bn=128, uk=1),           # seed default
    dict(bm=128, bn=256, uk=8),
    dict(bm=8, bn=256, uk=0),             # decode-M tiles
    dict(bm=256, bn=128, uk=0),
]
DECODE_BLOCK_B = [1, 2, 4, 8]
# What the TPU heuristic runs on a cache miss (no tuned/tpu.json is
# committed yet). Every main-path shape compiles with these
# (tests/test_tpu_compile.py); choosing faster ones is the tuner's job.
TPU_GEMM_TILE = dict(bm=128, bn=256, bk=128, uk=8)
TPU_FUSED_TILE = dict(bm=128, bn=4096, uk=8)
TPU_DECODE_BLOCKS = {"block_b": 1}
TPU_PREFILL_BLOCKS = {"block_q": 8, "block_b": 1}
PREFILL_BLOCKS = [dict(block_q=bq, block_b=bb)
                  for bq in (4, 8, 16) for bb in (1, 4)]

# The GEMM buckets are tuned per lhs form (pl=1 packed wire-format lhs,
# pl=0 float chain-entry lhs): the two forms run different kernels on the
# 'vpu' route (binary_gemm_vpu vs the in-kernel-pack binary_gemm_vpu_packed),
# so one timing cannot stand in for both.
_GEMM_SHAPES = [
    dict(m=4, n=64, kw=2),        # smoke decode projections
    dict(m=8, n=128, kw=2),
    dict(m=32, n=128, kw=4),      # smoke prefill chunks
    dict(m=8, n=512, kw=16),
    dict(m=64, n=1024, kw=32),
    dict(m=256, n=2048, kw=64),   # prefill-scale GEMM
]
_FUSED_SHAPES = [
    dict(m=4, n=64, kw=2),
    dict(m=8, n=128, kw=2),
    dict(m=32, n=128, kw=4),
    dict(m=64, n=1024, kw=32),
]

# The shape buckets CI guarantees are tuned (--check fails on a gap):
# the committed benchmarks' shapes plus the smoke-family serving shapes.
STANDARD_SHAPES: dict[str, list[dict[str, int]]] = {
    "binary_gemm": [dict(s, pl=pl) for s in _GEMM_SHAPES for pl in (1, 0)],
    "binary_gemm_fused": [dict(s, pl=pl)
                          for s in _FUSED_SHAPES for pl in (1, 0)],
    "decode_attention": [
        dict(b=4, t=16, hkv=2, g=2, hd=16),    # smoke serving engine
        dict(b=8, t=128, hkv=2, g=4, hd=64),
        dict(b=8, t=512, hkv=2, g=4, hd=64),   # BENCH_decode_attention
    ],
    "prefill_attention": [
        dict(b=4, s=8, t=16, hkv=2, g=2, hd=16),
        dict(b=4, s=8, t=128, hkv=2, g=4, hd=64),
        dict(b=8, s=16, t=512, hkv=2, g=4, hd=64),
    ],
    # paged twins: same attention shapes addressed through a page table
    # over a shared pool (p pages of ps tokens, t = pages-per-slot * ps)
    "decode_attention_paged": [
        dict(b=4, t=16, ps=4, p=16, hkv=2, g=2, hd=16),
        dict(b=8, t=128, ps=8, p=128, hkv=2, g=4, hd=64),
        dict(b=8, t=512, ps=8, p=512, hkv=2, g=4, hd=64),
    ],
    "prefill_attention_paged": [
        dict(b=4, s=8, t=16, ps=4, p=16, hkv=2, g=2, hd=16),
        dict(b=4, s=8, t=128, ps=8, p=128, hkv=2, g=4, hd=64),
    ],
}


def _pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length() if x > 1 else 1


def bucket(shape: dict[str, int]) -> dict[str, int]:
    """Round size-like dims up to the next power of two; keep structural
    dims exact. Tuning happens at the bucket shape, so one cache entry
    covers every shape that rounds into it."""
    return {k: (_pow2(v) if k in _BUCKETED else int(v))
            for k, v in shape.items()}


def bucket_key(shape: dict[str, int]) -> str:
    return "_".join(f"{k}{v}" for k, v in sorted(bucket(shape).items()))


def cache_path(backend: str | None = None) -> Path:
    return TUNED_DIR / f"{backend or jax.default_backend()}.json"


@functools.lru_cache(maxsize=4)
def _load(path_str: str, _mtime: float) -> dict[str, Any]:
    with open(path_str) as f:
        return json.load(f)


def load_cache(backend: str | None = None) -> dict[str, Any]:
    p = cache_path(backend)
    if not p.exists():
        return {}
    return _load(str(p), p.stat().st_mtime)


def _heuristic(kernel: str, shape: dict[str, int]) -> tuple[str, dict]:
    """Cache-miss fallback: a conservative per-backend guess. On CPU the
    Pallas kernels run in interpret mode, so the compiled packed
    formulation ('xla') wins small/medium shapes and the plain ±1 float
    matmul wins once the operands are huge (XLA's native GEMM outruns the
    unfused popcount expression there); on TPU the Pallas kernels are the
    default and the tuner refines their block shapes."""
    if jax.default_backend() == "cpu":
        if kernel in ("binary_gemm", "binary_gemm_fused"):
            m, n, kw = shape["m"], shape["n"], shape["kw"]
            return ("xla", {}) if m * n * kw <= (1 << 23) else ("float", {})
        return "xla", {}
    if kernel == "binary_gemm":
        return "vpu", dict(TPU_GEMM_TILE)
    if kernel == "binary_gemm_fused":
        return "vpu", dict(TPU_FUSED_TILE)
    if kernel in ("decode_attention", "decode_attention_paged"):
        return "pallas", dict(TPU_DECODE_BLOCKS)
    if kernel in ("prefill_attention", "prefill_attention_paged"):
        return "pallas", dict(TPU_PREFILL_BLOCKS)
    raise ValueError(f"unknown kernel: {kernel}")


# get_route misses, for tooling: maps (kernel, key) -> shape dict.
misses: dict[tuple[str, str], dict[str, int]] = {}
# Every resolution get_route has made, (kernel, key) -> route. Dispatch
# runs at trace time, so this is what the traced programs really call.
resolved: dict[tuple[str, str], str] = {}

# Active route pins (kernel name -> route), installed by `route_override`.
# Highest dispatch priority: consulted before the tuned cache.
_ROUTE_OVERRIDE: dict[str, str] = {}

# Every packed kernel's GSPMD-partitionable realization: pallas_call is
# opaque to XLA's auto-sharding, so jit'd code tracing over *sharded
# global* operands (the mesh scheduler's admission path) must resolve to
# a plain-XLA formulation. 'xla' is the ref oracle — bit-exact with every
# other route by construction — so pinning it can never change tokens.
GSPMD_SAFE_ROUTES = {
    "binary_gemm": "xla", "binary_gemm_fused": "xla",
    "decode_attention": "xla", "decode_attention_paged": "xla",
    "prefill_attention": "xla", "prefill_attention_paged": "xla",
}


@contextlib.contextmanager
def route_override(**kernel_routes: str):
    """Pin `kernel -> route` for every get_route call inside the context.

    Overrides apply at *trace* time: keep the context open around the jit
    call whose traced code should resolve to the pinned routes (retraces
    outside the context fall back to the tuned cache). Nests; inner
    contexts win on conflicts and restore the outer pins on exit.
    """
    old = dict(_ROUTE_OVERRIDE)
    _ROUTE_OVERRIDE.update(kernel_routes)
    try:
        yield
    finally:
        _ROUTE_OVERRIDE.clear()
        _ROUTE_OVERRIDE.update(old)


def gspmd_safe():
    """route_override pinning every packed kernel to its GSPMD-safe route."""
    return route_override(**GSPMD_SAFE_ROUTES)


def get_route(kernel: str, **shape: int) -> tuple[str, dict]:
    """Resolve (route, kernel params) for a static shape. Pure Python on
    static ints — safe to call at trace time. An active `route_override`
    pin wins; then a cache hit; otherwise the backend heuristic (or, with
    REPRO_AUTOTUNE=1 outside a trace, tune the missing bucket now and
    persist it)."""
    route, params = _resolve(kernel, shape)
    resolved[(kernel, bucket_key(shape))] = route
    return route, params


def _resolve(kernel: str, shape: dict[str, int]) -> tuple[str, dict]:
    if kernel in _ROUTE_OVERRIDE:
        return _ROUTE_OVERRIDE[kernel], {}
    key = bucket_key(shape)
    entry = load_cache().get(kernel, {}).get(key)
    if entry is not None:
        return entry["route"], dict(entry.get("params", {}))
    misses[(kernel, key)] = dict(shape)
    if os.environ.get("REPRO_AUTOTUNE") == "1" and \
            jax.core.trace_ctx.is_top_level():
        entry = tune_bucket(kernel, bucket(shape))
        return entry["route"], dict(entry.get("params", {}))
    return _heuristic(kernel, shape)


# ---------------------------------------------------------------------------
# Tuning: candidates, oracle gating, timing, persistence
# ---------------------------------------------------------------------------
def candidates(kernel: str, shape: dict[str, int]) -> list[tuple[str, dict]]:
    """The full (route, params) lattice the tuner may pick for a bucket —
    also the lattice the property tests must cover."""
    if kernel == "binary_gemm":
        cands = [("xla", {}), ("float", {}), ("mxu", {})]
        cands += [("vpu", dict(t)) for t in GEMM_TILES]
    elif kernel == "binary_gemm_fused":
        cands = [("xla", {}), ("float", {})]
        cands += [("vpu", dict(t)) for t in FUSED_TILES]
    elif kernel in ("decode_attention", "decode_attention_paged"):
        cands = [("xla", {})]
        cands += [("pallas", {"block_b": bb}) for bb in DECODE_BLOCK_B
                  if bb <= shape["b"]]
    elif kernel in ("prefill_attention", "prefill_attention_paged"):
        cands = [("xla", {})]
        cands += [("pallas", dict(p)) for p in PREFILL_BLOCKS
                  if p["block_b"] <= shape["b"]]
    else:
        raise ValueError(f"unknown kernel: {kernel}")
    return cands


def _time_us(fn, *args) -> float:
    out = jax.block_until_ready(fn(*args))          # compile
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    once = time.perf_counter() - t0
    iters = max(1, min(30, int(0.03 / max(once, 1e-7))))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _roofline(fn, *args) -> dict | None:
    """Roofline placement of a route's compiled HLO: flops, HBM bytes,
    arithmetic intensity (flops/byte). Best-effort — None if the HLO cost
    model cannot parse this computation."""
    try:
        from repro.roofline.hlo import analyze
        txt = jax.jit(fn).lower(*args).compile().as_text()
        c = analyze(txt)
        flops, byt = c["flops"], c["hbm_bytes"]
        return {"flops": flops, "hbm_bytes": byt,
                "ai": round(flops / byt, 3) if byt else None}
    except Exception:
        return None


def _problem(kernel: str, shape: dict[str, int]):
    """Synthesize operands at the bucket shape + the oracle closure +
    per-candidate runner factory. Returns (args, oracle_fn, make_fn)."""
    from repro.core.bitpack import pack_bits
    from repro.kernels import (binary_gemm, decode_attention,
                               prefill_attention, ref)
    key = jax.random.PRNGKey(sum(shape.values()))
    ks = jax.random.split(key, 8)
    if kernel in ("binary_gemm", "binary_gemm_fused"):
        m, n, kw = shape["m"], shape["n"], shape["kw"]
        k = kw * 32
        # pl keys the lhs form: packed wire-format words (the bit-resident
        # chain) vs float activations (chain entry) — the 'vpu' route runs
        # a different kernel for each, so each form is timed as itself.
        if shape.get("pl", 1):
            a = jax.random.bits(ks[0], (m, kw), jnp.uint32)
            aw = a
        else:
            a = jax.random.normal(ks[0], (m, k))
            aw = pack_bits(a)
        b = jax.random.bits(ks[1], (n, kw), jnp.uint32)
        if kernel == "binary_gemm":
            args = (a, b)
            oracle = lambda a, b, aw=aw: ref.binary_matmul_packed_ref(
                aw, b, k)
            make = lambda route, p: (
                lambda a, b: binary_gemm.dispatch_binary_gemm(
                    a, b, k, route=route, **p))
            return args, oracle, make
        th = jax.random.randint(ks[2], (n,), -8, 8, jnp.int32)
        fl = jax.random.randint(ks[3], (n,), 0, 2, jnp.int32)
        args = (a, b, th, fl)
        oracle = lambda a, b, th, fl, aw=aw: ref.binary_matmul_fused_ref(
            aw, b, th, fl, k)
        make = lambda route, p: (
            lambda a, b, th, fl: binary_gemm.dispatch_binary_gemm_fused(
                a, b, th, fl, k, route=route, **p))
        return args, oracle, make
    if kernel == "decode_attention":
        b, t, hkv, g, hd = (shape[x] for x in ("b", "t", "hkv", "g", "hd"))
        q = jax.random.normal(ks[0], (b, 1, hkv * g, hd))
        kf = jax.random.normal(ks[1], (b, t, hkv, hd))
        vf = jax.random.normal(ks[2], (b, t, hkv, hd))
        lens = jax.random.randint(ks[3], (b,), 1, t + 1)
        args = (q, pack_bits(kf), pack_bits(vf),
                decode_attention.v_cache_scale(vf), lens)
        oracle = lambda *a: ref.decode_attention_packed_ref(*a)
        make = lambda route, p: (
            lambda *a: decode_attention.decode_attention_packed(
                *a, route=route, **p))
        return args, oracle, make
    if kernel == "prefill_attention":
        b, s, t, hkv, g, hd = (shape[x]
                               for x in ("b", "s", "t", "hkv", "g", "hd"))
        q = jax.random.normal(ks[0], (b, s, hkv * g, hd))
        kf = jax.random.normal(ks[1], (b, t, hkv, hd))
        vf = jax.random.normal(ks[2], (b, t, hkv, hd))
        kv_len = jax.random.randint(ks[3], (b,), s, t + 1)
        args = (q, pack_bits(kf), pack_bits(vf),
                decode_attention.v_cache_scale(vf), kv_len, kv_len - s)
        oracle = lambda *a: ref.prefill_attention_packed_ref(*a)
        make = lambda route, p: (
            lambda *a: prefill_attention.prefill_attention_packed(
                *a, route=route, **p))
        return args, oracle, make
    if kernel in ("decode_attention_paged", "prefill_attention_paged"):
        decode = kernel == "decode_attention_paged"
        b, ps, hkv, g, hd = (shape[x] for x in ("b", "ps", "hkv", "g", "hd"))
        np_ = max(1, shape["t"] // ps)
        t = np_ * ps
        p_pool = max(shape["p"], b * np_)
        s = 1 if decode else shape["s"]
        q = jax.random.normal(ks[0], (b, s, hkv * g, hd))
        kf = jax.random.normal(ks[1], (b, t, hkv, hd))
        vf = jax.random.normal(ks[2], (b, t, hkv, hd))
        kp, vp = pack_bits(kf), pack_bits(vf)
        hdw = kp.shape[-1]
        # scatter the contiguous cache into a shuffled pool: the kernels
        # must pay the real gather indirection the tuner is timing
        perm = jax.random.permutation(
            ks[4], p_pool)[:b * np_].reshape(b, np_).astype(jnp.int32)
        k_pool = jnp.zeros((p_pool, ps, hkv, hdw), jnp.uint32) \
            .at[perm.reshape(-1)].set(kp.reshape(b * np_, ps, hkv, hdw))
        v_pool = jnp.zeros((p_pool, ps, hkv, hdw), jnp.uint32) \
            .at[perm.reshape(-1)].set(vp.reshape(b * np_, ps, hkv, hdw))
        vs = decode_attention.v_cache_scale(vf)
        lens = jax.random.randint(ks[3], (b,), s, t + 1)
        if decode:
            args = (q, k_pool, v_pool, vs, perm, lens)
            oracle = lambda *a: ref.decode_attention_packed_paged_ref(*a)
            make = lambda route, p: (
                lambda *a: decode_attention.decode_attention_packed_paged(
                    *a, route=route, **p))
        else:
            args = (q, k_pool, v_pool, vs, perm, lens, lens - s)
            oracle = lambda *a: ref.prefill_attention_packed_paged_ref(*a)
            make = lambda route, p: (
                lambda *a: prefill_attention.prefill_attention_packed_paged(
                    *a, route=route, **p))
        return args, oracle, make
    raise ValueError(f"unknown kernel: {kernel}")


def tune_bucket(kernel: str, shape: dict[str, int],
                verbose: bool = False) -> dict:
    """Tune one bucket: gate every candidate bit-exact vs the oracle, time
    the survivors, persist + return the winning cache entry."""
    shape = bucket(shape)
    args, oracle, make = _problem(kernel, shape)
    want = np.asarray(jax.jit(oracle)(*args))
    rows = []
    for route, params in candidates(kernel, shape):
        fn = jax.jit(make(route, params))
        got = np.asarray(fn(*args))
        if not np.array_equal(want, got):   # pragma: no cover - safety net
            raise AssertionError(
                f"{kernel} candidate {route} {params} is NOT bit-exact vs "
                f"ref.py at {shape} — refusing to tune a wrong kernel")
        us = _time_us(fn, *args)
        rows.append((us, route, params))
        if verbose:
            print(f"    {route:7s} {json.dumps(params):40s} {us:10.1f} us")
    rows.sort(key=lambda r: r[0])
    us, route, params = rows[0]
    entry = {
        "route": route, "params": params, "us": round(us, 2),
        "timings": {f"{r}:{json.dumps(p, sort_keys=True)}": round(u, 2)
                    for u, r, p in rows},
        "roofline": _roofline(make(route, params), *args),
    }
    _persist(kernel, bucket_key(shape), entry)
    if verbose:
        rl = entry["roofline"]
        ai = f", AI {rl['ai']} flop/B" if rl and rl.get("ai") else ""
        print(f"  -> {route} {params} @ {us:.1f} us{ai}")
    return entry


def _persist(kernel: str, key: str, entry: dict) -> None:
    p = cache_path()
    data = dict(load_cache())
    data.setdefault("_meta", {"backend": jax.default_backend(),
                              "jax": jax.__version__})
    data.setdefault(kernel, {})[key] = entry
    p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    _load.cache_clear()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def _cli_tune(force: bool) -> int:
    cache = load_cache()
    for kernel, shapes in STANDARD_SHAPES.items():
        for shape in shapes:
            key = bucket_key(shape)
            if not force and key in cache.get(kernel, {}):
                print(f"{kernel} {key}: cached "
                      f"({cache[kernel][key]['route']})")
                continue
            print(f"{kernel} {key}: tuning...")
            tune_bucket(kernel, shape, verbose=True)
    return 0


def _cli_check() -> int:
    """CI gate: every standard shape must have a committed cache entry for
    this backend. Exit 1 with instructions otherwise."""
    cache = load_cache()
    missing = [(k, bucket_key(s)) for k, shapes in STANDARD_SHAPES.items()
               for s in shapes if bucket_key(s) not in cache.get(k, {})]
    if missing:
        print(f"tune cache {cache_path()} is missing "
              f"{len(missing)} standard shape(s):")
        for k, key in missing:
            print(f"  {k}: {key}")
        print("run `python -m repro.kernels.tune --tune` on this host and "
              "commit the updated cache.")
        return 1
    print(f"tune cache {cache_path().name}: "
          f"{sum(len(v) for k, v in cache.items() if k != '_meta')} "
          "entries, all standard shapes covered.")
    return 0


def _cli_show() -> int:
    cache = load_cache()
    meta = cache.get("_meta", {})
    print(f"backend={meta.get('backend', jax.default_backend())} "
          f"(cache: {cache_path()})")
    for kernel in sorted(k for k in cache if k != "_meta"):
        print(f"\n{kernel}")
        for key, e in sorted(cache[kernel].items()):
            rl = e.get("roofline") or {}
            ai = f"  AI={rl['ai']}" if rl.get("ai") else ""
            print(f"  {key:36s} -> {e['route']:6s} "
                  f"{json.dumps(e['params']):32s} {e['us']:>9.1f} us{ai}")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tune", action="store_true",
                    help="tune standard shapes for this backend")
    ap.add_argument("--force", action="store_true",
                    help="retune even if cached")
    ap.add_argument("--check", action="store_true",
                    help="fail if the committed cache misses standard shapes")
    ap.add_argument("--show", action="store_true",
                    help="print the tuned decision table")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.tune:
        return _cli_tune(args.force)
    if args.check:
        return _cli_check()
    if args.show:
        return _cli_show()
    ap.print_help()
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
