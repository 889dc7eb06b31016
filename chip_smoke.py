#!/usr/bin/env python3
"""Smoke run of packed 1-bit serving on a TPU.

    python chip_smoke.py [--seed N]      # one chip
    python chip_smoke.py --four-chips    # the multi-chip paths, four chips

One chip: musicgen-large at its published widths (48 layers, d_model
2048, 32 heads x 64, d_ff 8192, vocab 2048), random weights from `--seed`,
frozen to packed sign bits with a bit-resident KV cache (`kv_bits=1`),
served through `ServingEngine` -> `Scheduler` with chunked admission, the
paged pool and the prefix cache. About eight greedy requests (two share a
512-token prefix) are served, then served again by a second engine whose
kernels are pinned to the XLA oracle routes; the tokens must be identical.
The packed kernels the paged engine does not call (the fused-epilogue
GEMM, the contiguous-cache attention) are checked bit for bit against
their oracles at the same widths.

Four chips (`--four-chips`, and nothing else): the same requests through
a `shard_map` decode burst on a 4x1 ('data', 'model') mesh and through a
4-replica `ReplicaServer`, each against one single-device engine; tokens
must be identical. Widths stay published, depth is cut to 4 layers.

Every time printed is a smoke time (set-up, compile, serving wall clock),
not a measurement. Exits non-zero when the device is not a TPU, when a
packed kernel on the path resolves to a non-Pallas route, when the runs
disagree, or when any phase raises. The last line of standard output is
one JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# routes that run a Pallas kernel on the chip
PALLAS = {"vpu", "pallas"}
PAGE, CHUNK, SLOTS, MAX_LEN, NEW_TOKENS = 128, 128, 8, 2048, 64


def fail(msg: str) -> None:
    print(f"chip_smoke FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileClock:
    """Sums JAX's own trace/lower/compile durations, so set-up and compile
    seconds can be told apart from serving seconds."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name in self.EVENTS:
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def make_requests(seed: int, vocab: int):
    """Eight greedy requests: six prompts of 128-1024 tokens and two that
    share a 512-token prefix. Returned as (first wave, second wave): the
    second sharer arrives after the first has retired, so its prefix is
    served from the prefix cache."""
    import numpy as np
    from repro.serving.engine import Request
    rng = np.random.default_rng(seed)
    tok = lambda n: rng.integers(0, vocab, n).astype(np.int32)  # noqa: E731
    prompts = [tok(int(n)) for n in rng.integers(128, 1025, 6)]
    prefix = tok(512)
    shared = [np.concatenate([prefix, tok(int(n))])
              for n in rng.integers(64, 257, 2)]
    reqs = [Request(p, max_new_tokens=NEW_TOKENS) for p in prompts + shared]
    return reqs[:7], reqs[7:]


def serve_waves(engine, waves):
    out = []
    for wave in waves:
        out += engine.serve(wave)
    bad = [c for c in out if c.status != "completed"]
    if bad:
        fail(f"request {bad[0].rid} ended {bad[0].status}: {bad[0].error}")
    return out


def first_divergence(want, got):
    for i, (a, b) in enumerate(zip(want, got)):
        if len(a) != len(b) or (a != b).any():
            n = min(len(a), len(b))
            j = next((j for j in range(n) if a[j] != b[j]), n)
            return f"request {i} token {j}: {a[j:j + 4]} vs {b[j:j + 4]}"
    return None


def build_params(jax, cfg, seed: int):
    """Random masters, frozen to packed sign bits, as ONE jitted program on
    the default device: XLA fuses init into the pack, so the ~9.7 GB of
    fp32 masters never exist at once (compile-time temp is ~0.8 GB at
    musicgen-large widths) and only the packed tree stays resident."""
    from repro.models.api import get_model
    model = get_model(cfg)
    params = jax.jit(lambda k: model.freeze(model.init(k)))(
        jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def engine_kw(seed: int) -> dict:
    return dict(max_len=MAX_LEN, freeze=True, slots=SLOTS, seed=seed,
                kv_bits=1, prefill_chunk=CHUNK, page_size=PAGE,
                prefix_cache=True)


def check_pallas(tune, label: str) -> dict:
    """Every packed kernel resolved while tracing `label` ran on Pallas."""
    routes = {f"{k}[{key}]": r for (k, key), r in sorted(tune.resolved.items())}
    for name, route in routes.items():
        print(f"  route {name}: {route}")
    bad = {n: r for n, r in routes.items() if r not in PALLAS}
    if not routes or bad:
        fail(f"{label}: packed kernels not on a Pallas route: {bad or 'none'}")
    return routes


def kernel_phase(jax, seed: int, cfg) -> None:
    """The packed kernels at musicgen-large widths, Pallas (the TPU
    heuristic's blocks) against the XLA oracle route, bit for bit — the
    fused-epilogue GEMM and the contiguous-cache attention are not on the
    paged engine's path, so they are checked here."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.bitpack import packed_width
    from repro.kernels import tune
    from repro.kernels.binary_gemm import (
        dispatch_binary_gemm, dispatch_binary_gemm_fused)
    from repro.kernels.decode_attention import (
        decode_attention_packed, decode_attention_packed_paged)
    from repro.kernels.prefill_attention import (
        prefill_attention_packed, prefill_attention_packed_paged)

    d, f, hkv, hd = cfg.d_model, cfg.d_ff, cfg.n_kv_heads, cfg.head_dim
    hdw, n_pages = packed_width(hd), MAX_LEN // PAGE
    ks = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 32))
    bits = lambda *s: jax.random.bits(next(ks), s, jnp.uint32)  # noqa: E731
    normal = lambda *s: jax.random.normal(next(ks), s, jnp.bfloat16)  # noqa
    ints = lambda lo, hi, *s: jax.random.randint(  # noqa: E731
        next(ks), s, lo, hi, jnp.int32)

    cases = {}    # name -> (fn(route, *operands), operands)
    for k, n in ((d, f), (f, d)):
        w = bits(n, packed_width(k))
        th, fl = ints(-64, 64, n), ints(0, 2, n)
        for lhs, tag in ((bits(SLOTS, packed_width(k)), "bits"),
                         (normal(SLOTS, k), "f32")):
            cases[f"binary_gemm {k}->{n} {tag}"] = (
                lambda r, a, w, k=k: dispatch_binary_gemm(a, w, k, route=r),
                (lhs, w))
            cases[f"binary_gemm_fused {k}->{n} {tag}"] = (
                lambda r, a, w, th, fl, k=k: dispatch_binary_gemm_fused(
                    a, w, th, fl, k, route=r), (lhs, w, th, fl))
    vs = jnp.abs(normal(SLOTS, hkv)).astype(jnp.float32) + 0.1
    lens = ints(1, MAX_LEN + 1, SLOTS)
    kc, vc = bits(SLOTS, MAX_LEN, hkv, hdw), bits(SLOTS, MAX_LEN, hkv, hdw)
    kp, vp = (bits(SLOTS * n_pages, PAGE, hkv, hdw) for _ in range(2))
    pt = jax.random.permutation(next(ks), SLOTS * n_pages).reshape(
        SLOTS, n_pages).astype(jnp.int32)
    qd = normal(SLOTS, 1, cfg.n_heads, hd)
    qp = normal(1, CHUNK, cfg.n_heads, hd)
    kv_len = ints(CHUNK, MAX_LEN + 1, 1)
    cases["decode_attention"] = (
        lambda r, *a: decode_attention_packed(*a, route=r),
        (qd, kc, vc, vs, lens))
    cases["decode_attention_paged"] = (
        lambda r, *a: decode_attention_packed_paged(*a, route=r),
        (qd, kp, vp, vs, pt, lens))
    cases["prefill_attention"] = (
        lambda r, *a: prefill_attention_packed(*a, route=r),
        (qp, kc[:1], vc[:1], vs[:1], kv_len, kv_len - CHUNK))
    cases["prefill_attention_paged"] = (
        lambda r, *a: prefill_attention_packed_paged(*a, route=r),
        (qp, kp, vp, vs[:1], pt[:1], kv_len, kv_len - CHUNK))
    tune.resolved.clear()
    for name, (fn, operands) in cases.items():
        got = np.asarray(jax.jit(functools.partial(fn, None))(*operands))
        want = np.asarray(jax.jit(functools.partial(fn, "xla"))(*operands))
        if not np.array_equal(got, want):
            fail(f"kernel {name}: Pallas and oracle differ in "
                 f"{int((got != want).sum())} of {got.size} values")
        print(f"kernel {name}: {got.shape} bit-exact vs oracle")
    check_pallas(tune, "kernel phase")


def one_chip(jax, seed: int, clock: CompileClock) -> None:
    from repro.configs import get_config
    from repro.kernels import tune
    from repro.serving.engine import ServingEngine

    cfg = get_config("musicgen-large")
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads x {cfg.head_dim} (kv {cfg.n_kv_heads}), "
          f"d_ff {cfg.d_ff} {cfg.mlp}, vocab {cfg.vocab}")
    t0, c0 = time.perf_counter(), clock.seconds
    params = build_params(jax, cfg, seed)
    print(f"set-up: masters built and frozen on {jax.devices()[0]} in one "
          f"jitted program: {time.perf_counter() - t0:.1f} s "
          f"(compile {clock.seconds - c0:.1f} s) [smoke time]")

    waves = make_requests(seed, cfg.vocab)
    reqs = waves[0] + waves[1]
    print(f"requests: {len(reqs)} greedy, prompt tokens "
          f"{[len(r.prompt) for r in reqs]}, {NEW_TOKENS} new each")

    tune.resolved.clear()
    eng = ServingEngine(cfg, params, **engine_kw(seed))
    wb, cb = eng.resident_weight_bytes(), eng.resident_cache_bytes()
    print(f"resident weights: {wb}")
    print(f"resident cache: {cb}")
    t0, c0 = time.perf_counter(), clock.seconds
    done = serve_waves(eng, waves)
    wall, comp = time.perf_counter() - t0, clock.seconds - c0
    toks = [c.tokens for c in done]
    n_out = sum(len(t) for t in toks)
    print(f"serve (Pallas routes): {n_out} tokens out in {wall:.1f} s, of "
          f"which trace+compile {comp:.1f} s, serving {wall - comp:.1f} s "
          f"[smoke time]")
    print(f"prefix cache: second sharer served {done[-1].cached_tokens} "
          f"prompt tokens from cached pages")
    if done[-1].cached_tokens <= 0:
        fail("the shared prefix was not served from the prefix cache")
    print("routes the serving programs traced:")
    check_pallas(tune, "serving")
    print("kernel routes (ServingEngine.kernel_routes):")
    for name, route in eng.kernel_routes().items():
        print(f"  {name}: {route}")
    print(f"tune misses (heuristic used, no tuned cache for this backend): "
          f"{len(tune.misses)}")
    for (kernel, key) in sorted(tune.misses):
        print(f"  miss {kernel}[{key}]")

    t0, c0 = time.perf_counter(), clock.seconds
    with tune.route_override(**tune.GSPMD_SAFE_ROUTES):
        oracle = ServingEngine(cfg, params, **engine_kw(seed))
        want = [c.tokens for c in serve_waves(oracle, waves)]
    wall, comp = time.perf_counter() - t0, clock.seconds - c0
    print(f"serve (oracle routes): {wall:.1f} s, of which trace+compile "
          f"{comp:.1f} s [smoke time]")
    div = first_divergence(want, toks)
    if div:
        fail(f"Pallas and oracle-route tokens differ: {div}")
    print(f"tokens: identical to the oracle-route run ({n_out} tokens, "
          f"{len(reqs)} requests)")
    del oracle

    kernel_phase(jax, seed, cfg)


def four_chips(jax, seed: int, clock: CompileClock) -> None:
    from repro.configs import get_config
    from repro.kernels import tune
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.engine import ServingEngine
    from repro.serving.replica import ReplicaServer

    if len(jax.devices()) < 4:
        fail(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = get_config("musicgen-large").scaled(n_layers=4)
    print(f"model {cfg.name} at published widths, depth cut to "
          f"{cfg.n_layers} layers")
    params = build_params(jax, cfg, seed)
    waves = make_requests(seed, cfg.vocab)
    kw = engine_kw(seed)

    t0 = time.perf_counter()
    want = [c.tokens for c in serve_waves(ServingEngine(cfg, params, **kw),
                                          waves)]
    print(f"single device ({jax.devices()[0]}): {sum(map(len, want))} "
          f"tokens in {time.perf_counter() - t0:.1f} s [smoke time]")

    tune.resolved.clear()
    t0 = time.perf_counter()
    mesh = make_serving_mesh(4, 1)
    got = [c.tokens for c in serve_waves(
        ServingEngine(cfg, params, mesh=mesh, **kw), waves)]
    print(f"mesh data=4,model=1: {time.perf_counter() - t0:.1f} s "
          f"[smoke time]; routes traced (admission is pinned to the "
          f"GSPMD-safe oracle routes by design, the shard_map decode burst "
          f"runs the Pallas kernels per device):")
    routes = {f"{k}[{key}]": r for (k, key), r in sorted(tune.resolved.items())}
    for name, route in routes.items():
        print(f"  route {name}: {route}")
    if not any(n.startswith("decode_attention") and r in PALLAS
               for n, r in routes.items()):
        fail("the mesh decode burst did not run the Pallas attention kernel")
    div = first_divergence(want, got)
    if div:
        fail(f"mesh tokens differ from one device: {div}")
    print("mesh tokens: identical to one device")

    t0 = time.perf_counter()
    server = ReplicaServer(cfg, params, devices=jax.devices()[:4], **kw)
    got = []
    for wave in waves:
        got += server.generate(wave)
    print(f"4 replicas: {time.perf_counter() - t0:.1f} s [smoke time]")
    div = first_divergence(want, got)
    if div:
        fail(f"replica tokens differ from one device: {div}")
    print("replica tokens: identical to one device")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh and replica comparisons")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"the repo's sources are not next to this script ({SRC})")
    sys.path.insert(0, str(SRC))

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu" or jax.default_backend() != "tpu":
        fail(f"no TPU found (JAX's first device is {dev.platform})")
    # every kernel takes interpret mode only on the CPU backend
    # (`interpret = jax.default_backend() == "cpu"`), so from here on the
    # Pallas routes compile for the chip
    print(f"device: {dev.device_kind} x {len(jax.devices())} "
          f"({dev.platform}), jax {jax.__version__}")
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock(jax)
    t_start = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(jax, args.seed, clock)
    stats = dev.memory_stats() or {}
    print(f"peak bytes in use on {dev}: {stats.get('peak_bytes_in_use')}")
    print(f"total {time.perf_counter() - t_start:.1f} s, trace+compile "
          f"{clock.seconds:.1f} s (summed over threads), persistent cache "
          f"hits {clock.cache_hits} [smoke time]")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
