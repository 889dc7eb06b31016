"""Serving launcher: loads (or initializes) a model and serves synthetic
requests — either one static batch through the legacy engine path, or a
queue of mixed-length requests with Poisson arrivals through the
continuous-batching scheduler.

  PYTHONPATH=src python -m repro.launch.serve --arch musicgen-large --smoke
  PYTHONPATH=src python -m repro.launch.serve --arch musicgen-large --smoke \
      --queue --arrival-rate 8 --batch 12
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests to serve")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="prompt length (max length in --queue mode: "
                         "lengths are drawn from [prompt_len//4, prompt_len])")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--ckpt", default=None, help="checkpoint dir to load")
    ap.add_argument("--freeze", action="store_true",
                    help="freeze binary weights to packed 1-bit form and "
                         "serve from XNOR+popcount")
    ap.add_argument("--kv-bits", type=int, default=0, choices=(0, 1),
                    help="1 = bit-resident KV cache: K/V stored as packed "
                         "sign bitplanes, decode attention via XOR+popcount")
    ap.add_argument("--queue", action="store_true",
                    help="continuous-batching mode: mixed-length requests "
                         "stream through the slot scheduler")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked admission: prompts advance through the "
                         "slot cache in fixed-shape chunks of this many "
                         "tokens, interleaved with decode bursts (0 = "
                         "whole-prompt admission)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache: fixed pages of this many tokens "
                         "in a shared refcounted pool, addressed through "
                         "per-slot page tables (0 = contiguous slot cache; "
                         "attention families, needs --prefill-chunk)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="page-pool size (0 = slots * pages-per-slot)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prefix cache over full KV pages: "
                         "requests sharing a prompt prefix pin the same "
                         "pages zero-copy and prefill only their unseen "
                         "suffix (needs --page-size)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="requests/second Poisson arrivals in --queue mode "
                         "(0 = submit everything upfront)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots in --queue mode")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="serve over a device mesh: 'data=D,model=M' (or "
                         "'D,M'/'D'). The scheduler shards its slots over "
                         "the data axis (shard_map decode burst); a model "
                         "axis replicates serving state and is reserved "
                         "for the tensor-parallel kernel wrappers. "
                         "Simulate devices on CPU with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--replicas", type=int, default=0,
                    help="data-parallel replica serving: one request queue "
                         "fans out to this many single-device engines "
                         "(serving.replica; exclusive with --mesh/--queue)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request TTFT deadline in seconds (--queue "
                         "mode): requests still queued past it are shed "
                         "before burning prefill compute (0 = none)")
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="bounded admission queue: submissions beyond this "
                         "many queued requests are rejected with "
                         "backpressure (0 = unbounded)")
    ap.add_argument("--inject-faults", default="",
                    help="deterministic fault plan, comma-separated "
                         "kind@site:index[*times][:param] entries, e.g. "
                         "'device_error@burst:2*3,slow@burst:6:0.05,"
                         "death@replica0:1' (serving.faults)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import get_config
    from repro.configs.smoke import smoke_config
    from repro.models.api import get_model
    from repro.serving.engine import Request, ServingEngine

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = get_model(cfg)
    if args.inject_faults:
        from repro.serving.faults import parse_plan
        args.fault_plan = parse_plan(args.inject_faults)
        print(f"fault plan armed: {len(args.fault_plan.faults)} fault(s) — "
              f"{args.inject_faults}")
    else:
        args.fault_plan = None
    key = jax.random.PRNGKey(args.seed)
    if args.ckpt:
        from repro.checkpoint.manager import CheckpointManager
        mgr = CheckpointManager(args.ckpt)
        like = jax.eval_shape(model.init, key)
        params = mgr.restore(mgr.latest_step(), like)
    else:
        params = model.init(key)

    if args.replicas:
        _serve_replicas(cfg, params, rng_seed=args.seed, args=args)
        return

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serving_mesh, parse_mesh
        data, model_ax = parse_mesh(args.mesh)
        mesh = make_serving_mesh(data, model_ax)
        print(f"serving mesh: data={data} x model={model_ax} over "
              f"{data * model_ax} of {len(jax.devices())} devices")

    eng = ServingEngine(cfg, params,
                        max_len=args.prompt_len + args.max_new + 1,
                        freeze=args.freeze, slots=args.slots, seed=args.seed,
                        kv_bits=args.kv_bits, mesh=mesh,
                        prefill_chunk=args.prefill_chunk or None,
                        page_size=args.page_size or None,
                        pool_pages=args.pool_pages or None,
                        prefix_cache=args.prefix_cache,
                        queue_cap=args.queue_cap or None,
                        fault_plan=args.fault_plan)
    if eng.frozen:
        rb = eng.resident_weight_bytes()
        total = rb["binary"] + rb["other"]
        print(f"serving packed 1-bit weights: {total/1e6:.2f} MB resident "
              f"total = {rb['binary']/1e6:.2f} MB binary layers (packed) "
              f"+ {rb['other']/1e6:.2f} MB non-binary (embeddings, norms, "
              f"recurrence dynamics)")
        cb = eng.resident_cache_bytes()
        print(f"kv cache / state ({eng.slots} slots x {eng.max_len}): "
              f"{cb['total']/1e6:.3f} MB resident = {cb['packed']/1e6:.3f} MB "
              f"packed bitplanes (kv_bits={eng.cfg.kv_bits}) + "
              f"{cb['float']/1e6:.3f} MB float (fp K/V, V scales, recurrent "
              f"state)")
        pp = cb.get("page_pool")
        if pp is None and eng.page_size:
            pp = eng.scheduler().page_stats()
        if pp:
            pinned = pp.get("pinned_by_prefix", 0)
            print(f"page pool: {pp['pages']} pages x {pp['page_size']} "
                  f"tokens = {pp['allocated']} allocated "
                  f"({pinned} pinned by prefix tree) + {pp['free']} free")
        if mesh is not None:
            # live per-device residency: shards of the placed arrays, so
            # batch-sharded cache/state leaves count 1/data-th per device
            # while packed weights and paged pools replicate
            for dev, b in sorted(eng.resident_bytes_per_device().items()):
                print(f"  {dev}: {b['total']/1e6:.3f} MB resident = "
                      f"{b['weights']/1e6:.3f} MB weights + "
                      f"{b['cache']/1e6:.3f} MB cache/pool + "
                      f"{b['state']/1e6:.3f} MB serving state")
        for name, (route, params) in eng.kernel_routes().items():
            extra = f" {params}" if params else ""
            print(f"kernel route {name}: {route}{extra}")
    rng = np.random.default_rng(args.seed)

    if args.queue:
        _serve_queue(eng, cfg, rng, args)
        return

    reqs = [Request(prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=args.max_new)
            for _ in range(args.batch)]
    outs = eng.generate(reqs)
    for i, o in enumerate(outs):
        print(f"req {i}: {o.tolist()}")
    print("stats:", eng.scheduler().stats)


def _serve_replicas(cfg, params, *, rng_seed: int, args) -> None:
    """Replica fan-out mode: one queue of `--batch` requests round-robins
    over `--replicas` single-device engines (serving.replica)."""
    from repro.serving.engine import Request
    from repro.serving.replica import ReplicaServer, devices_needed

    devs = jax.devices()
    assert args.replicas <= len(devs), \
        f"--replicas {args.replicas} > {len(devs)} devices " \
        f"(simulate with XLA_FLAGS=--xla_force_host_platform_device_count=N)"
    srv = ReplicaServer(cfg, params, devices=devs[:args.replicas],
                        fault_plan=args.fault_plan,
                        max_len=args.prompt_len + args.max_new + 1,
                        freeze=args.freeze, slots=args.slots, seed=args.seed,
                        kv_bits=args.kv_bits,
                        prefill_chunk=args.prefill_chunk or None,
                        page_size=args.page_size or None,
                        pool_pages=args.pool_pages or None,
                        prefix_cache=args.prefix_cache)
    rng = np.random.default_rng(rng_seed)
    lo = max(1, args.prompt_len // 4)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(lo, args.prompt_len + 1)),
                                        dtype=np.int32),
                    max_new_tokens=args.max_new)
            for _ in range(args.batch)]
    t0 = time.time()
    outs = srv.generate(reqs)
    wall = time.time() - t0
    st = srv.stats()
    print(f"{st['replicas']} replicas ({st['healthy']} healthy, "
          f"{st['failovers']} failover rounds) served {len(outs)} requests "
          f"in {wall:.3f}s | {st['tokens_out']/wall:.1f} tok/s aggregate")
    for e in st["per_replica"]:
        line = (f"  {e['device']}: {e['weight_bytes']/1e6:.2f} MB weights + "
                f"{e['cache_bytes']/1e6:.3f} MB cache")
        s = e.get("scheduler")
        if s:
            line += (f" | {s['completed']} reqs, {s['tokens_out']} tokens, "
                     f"decode {s['decode_s']:.3f}s")
        print(line)
    if args.freeze:
        # the fit argument, in device units: a per-device budget sized so
        # the fp32 masters would need 8 devices vs what packed needs
        wb = st["per_replica"][0]["weight_bytes"]
        unpacked = sum(int(np.prod(l.shape)) * 4 for l in
                       jax.tree.leaves(jax.eval_shape(lambda: params)))
        budget = -(-unpacked // 8)
        print(f"fit at a {budget/1e6:.2f} MB/device budget (float needs "
              f"{devices_needed(unpacked, budget)}): packed replica fits in "
              f"{devices_needed(wb, budget)} device(s)")


def _serve_queue(eng, cfg, rng, args) -> None:
    """Stream `--batch` mixed-length requests through the scheduler with
    exponential inter-arrival gaps (`--arrival-rate` req/s). `--deadline`
    sets each request's TTFT deadline (late ones shed); `--queue-cap`
    bounds the admission queue (overflow rejected with backpressure);
    `--inject-faults` arms the scheduler's fault plan."""
    from repro.serving.engine import Request
    from repro.serving.faults import QueueFull

    sched = eng.scheduler()
    lo = max(1, args.prompt_len // 4)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab,
                                        int(rng.integers(lo, args.prompt_len + 1)),
                                        dtype=np.int32),
                    max_new_tokens=int(rng.integers(1, args.max_new + 1)),
                    deadline_s=args.deadline or None)
            for _ in range(args.batch)]
    if args.arrival_rate > 0:
        gaps = rng.exponential(1.0 / args.arrival_rate, size=len(reqs))
        arrive_at = np.cumsum(gaps)
    else:
        arrive_at = np.zeros(len(reqs))

    t0 = time.time()
    pending = list(zip(arrive_at, reqs))
    lats, ttfts, itls = [], [], []
    while pending or not sched.idle:
        now = time.time() - t0
        while pending and pending[0][0] <= now:
            _, req = pending.pop(0)
            try:
                rid = sched.submit(req)
            except QueueFull:
                print(f"t={now:7.3f}s REJECT (queue at cap "
                      f"{sched.queue_cap}) prompt={req.prompt.size}")
                continue
            print(f"t={now:7.3f}s submit rid={rid} "
                  f"prompt={req.prompt.size} max_new={req.max_new_tokens}")
        if sched.idle and pending:
            time.sleep(min(0.01, pending[0][0] - now))
            continue
        # non-drain poll: yield at every completion so slots stay
        # admittable for requests arriving mid-flight
        for c in sched.poll(drain=not pending):
            if c.status != "completed":
                print(f"t={time.time()-t0:7.3f}s {c.status.upper():6s} "
                      f"rid={c.rid}" +
                      (f" ({c.error})" if c.error else ""))
                continue
            lats.append(c.latency)
            ttfts.append(c.ttft)
            itls.extend(c.itl.tolist())
            print(f"t={time.time()-t0:7.3f}s done   rid={c.rid} "
                  f"tokens={c.tokens.size} latency={c.latency*1e3:.1f}ms "
                  f"ttft={c.ttft*1e3:.1f}ms")
    wall = time.time() - t0
    lats = np.asarray(sorted(lats))
    ttfts = np.asarray(ttfts)
    # wall times below are honest compute times: the scheduler syncs the
    # device before every clock read (prefill_s / decode_s / per-token)
    itl_p99 = f"{np.percentile(itls, 99)*1e3:.1f}ms" if itls else "n/a"
    if lats.size:
        print(f"served {len(lats)} requests in {wall:.3f}s | "
              f"{sched.stats['tokens_out']/wall:.1f} tok/s | "
              f"latency p50 {np.percentile(lats, 50)*1e3:.1f}ms "
              f"p99 {np.percentile(lats, 99)*1e3:.1f}ms | "
              f"ttft p50 {np.percentile(ttfts, 50)*1e3:.1f}ms "
              f"p99 {np.percentile(ttfts, 99)*1e3:.1f}ms | "
              f"inter-token p99 {itl_p99}")
    s = sched.stats
    if any(s[k] for k in ("shed", "errors", "rejected", "burst_retries",
                          "invariant_violations")):
        print(f"resilience: {s['shed']} shed, {s['errors']} errored, "
              f"{s['rejected']} rejected at cap, {s['burst_retries']} "
              f"burst retries, {s['invariant_violations']} invariant "
              f"violations (degraded to cache bypass)")
    print(f"decode steps {sched.decode_steps()} "
          f"bursts {sched.stats['bursts']} | "
          f"prefill {sched.stats['prefill_s']:.3f}s "
          f"decode {sched.stats['decode_s']:.3f}s | "
          f"chunked admission: {sched.prefill_chunk or 'off'} "
          f"({sched.prefill_shape_count} prefill shapes compiled)")
    ps = sched.page_stats()
    if ps is not None:
        line = (f"page pool: {ps['allocated']}/{ps['pages']} pages "
                f"allocated ({ps.get('pinned_by_prefix', 0)} pinned by "
                f"prefix tree)")
        tree = ps.get("prefix_tree")
        if tree is not None:
            line += (f" | prefix cache: {tree['hits']}/{tree['lookups']} "
                     f"hits, {sched.stats['prefill_tokens_saved']} prompt "
                     f"tokens served zero-copy, {tree['evicted']} evicted")
        print(line)


if __name__ == "__main__":
    main()
