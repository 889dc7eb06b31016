"""One run of one cell: set-up, open-loop window, trace, check.

    set-up   weights from the seed (one jitted call), the engine, a compile
             pass over every shape the traffic uses, then `warmup_s` of
             the cell's own traffic (its own sub-seed) so that the window
             opens on a loaded system
    window   `seconds` of traffic: Poisson arrivals submitted between
             polls once due, or a backlog kept at depth; poll(drain=False)
    trace    (--trace 1) a few seconds inside the window under the JAX
             profiler, with the harness's own spans on the same clock
    after    Poisson arrivals go on past the window's end until every
             request due in the window is delivered (at most DRAIN_S), so
             each latency counts the whole wait under the same load
    check    memory peak read, the engine freed, the plain reference run
             over a seeded sample of the requests delivered since the
             window opened (as many as the configuration's check names
             under `checked_requests`), and every compared number held to
             its limit
"""
from __future__ import annotations

import contextlib
import gc
import math
import shutil
import tempfile
import time

import numpy as np

from chipbench import costs, files, probe, program, stats, trace, traffic
from chipbench import weights

COMPILE_PASS_NEW = 12          # tokens each compile-pass request asks for
TRACE_AT, TRACE_S = 0.4, 4.0   # traced share of the window: start, seconds
DRAIN_S = 120.0                # longest the client serves past the window
PALLAS_ROUTES = {"vpu", "pallas"}   # routes that run a Pallas kernel


class CompileClock:
    """Sums JAX's own trace, lower and compile durations and counts
    persistent-cache hits, so compile time inside a span can be told."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name in self.EVENTS:
            self.seconds += secs
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def read(self) -> tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


class Client:
    """Open-loop client: submits each request once due, between polls,
    and keeps one record per request on the harness's clock."""

    def __init__(self, sched, clk, spans: bool):
        self.sched, self.clk = sched, clk
        self.records: dict[int, dict] = {}
        self.spans = spans
        self.hooks: list = []            # (time, fn) run between polls

    def span(self, name: str):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def submit(self, req: traffic.Req, due: float, phase: int) -> None:
        from repro.serving.scheduler import Request
        with self.span("client.submit"):
            rid = self.sched.submit(Request(req.prompt,
                                            max_new_tokens=req.max_new))
        self.records[rid] = {"rid": rid, "due": due, "submit": self.clk(),
                             "plen": int(req.prompt.size), "n": req.max_new,
                             "prompt": req.prompt, "phase": phase,
                             "done": None}

    def poll(self) -> None:
        with self.span("scheduler.poll"):
            comps = self.sched.poll(drain=False)
        t = self.clk()
        for c in comps:
            r = self.records[c.rid]
            r.update(done=t, status=c.status, tokens=np.asarray(c.tokens),
                     ttft=c.ttft, ttft_wall=c.ttft_wall,
                     cached=c.cached_tokens)

    def _run_hooks(self) -> None:
        now = self.clk()
        while self.hooks and self.hooks[0][0] <= now:
            self.hooks.pop(0)[1]()

    def run_poisson(self, reqs: list[traffic.Req], start: float,
                    end: float, phase: int, until=None) -> None:
        """Serve until `end`, or until `until()` holds."""
        i = 0
        while True:
            now = self.clk()
            while i < len(reqs) and start + reqs[i].due <= now:
                self.submit(reqs[i], start + reqs[i].due, phase)
                i += 1
            self._run_hooks()
            if now >= end or (until is not None and until()):
                return
            if self.sched.idle:
                nxt = start + reqs[i].due if i < len(reqs) else end
                if self.hooks:
                    nxt = min(nxt, self.hooks[0][0])
                with self.span("client.sleep"):
                    time.sleep(max(0.0, min(nxt, end) - now))
                continue
            self.poll()

    def outstanding(self) -> int:
        return sum(r["done"] is None for r in self.records.values())

    def run_backlog(self, reqs: list[traffic.Req], target: int, end: float,
                    phase: int) -> None:
        pool = iter(reqs)
        while True:
            now = self.clk()
            while self.outstanding() < target:
                req = next(pool, None)
                if req is None:
                    raise RuntimeError("backlog pool ran dry: raise the "
                                       "mix's pool_per_s")
                self.submit(req, now, phase)
            self._run_hooks()
            if now >= end:
                return
            self.poll()


def _phase_reqs(mix: dict, vocab: int, seed: int, phase: int, span: float,
                slots: int) -> list[traffic.Req]:
    arr = mix["arrivals"]
    n = 0
    if arr["kind"] == "backlog":
        n = int(slots * (1 + arr["depth_per_slot"])
                + math.ceil(span * arr["pool_per_s"]))
    return traffic.phase(mix, vocab, seed, phase, span, n_backlog=n)


def compile_pass(client: Client, mix: dict, cfg: dict, seed: int) -> None:
    """Two short requests whose prompts take a mid and a final chunk: the
    first decodes alone (unbounded bursts), the second admits while it
    runs (bounded bursts). Every program the window runs compiles here."""
    c = cfg["serving"]["prefill_chunk"]
    rng = np.random.default_rng([seed, 3])
    vocab = cfg["model"]["vocab"]
    for _ in range(2):
        req = traffic.Req(rng.integers(0, vocab, c + 1).astype(np.int32),
                          COMPILE_PASS_NEW, 0.0)
        client.submit(req, client.clk(), phase=-1)
    while client.outstanding():
        client.poll()


def _sample(records: list[dict], seed: int, k: int) -> list[dict]:
    """The longest delivered request and k - 1 others drawn from the
    seed."""
    if not records:
        return []
    order = sorted(records, key=lambda r: (-r["n"], r["rid"]))
    rest = order[1:]
    rng = np.random.default_rng([seed, 4])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)),
                      replace=False) if rest else []
    return [order[0]] + [rest[i] for i in sorted(pick)]


def reference_gaps(cfg: dict, seed: int, sample: list[dict],
                   control: bool = False):
    """Per served token of the sample, the gap by which its logit lies
    below the reference's best at that position (0 where it is the
    reference's first choice). With `control`, also the gaps of the tokens
    that the control (the reference one precision lower) puts first at
    the same positions of the same prompts and served tokens: the control
    in the program's place."""
    ref = files.load_module("reference", cfg["reference"])
    s_pad = max(r["plen"] + r["n"] for r in sample)
    s_pad = -(-s_pad // 256) * 256
    toks = np.zeros((len(sample), s_pad), np.int32)
    for i, r in enumerate(sample):
        seq = np.concatenate([r["prompt"], r["tokens"][:-1]])
        toks[i, :seq.size] = seq
    out = ref.logits(cfg["model"], seed, toks)
    low = ref.logits(cfg["model"], seed, toks, low=True) if control else None

    def gaps(rows, pick):
        return np.max(rows, axis=-1) - np.take_along_axis(
            rows, pick[:, None], -1)[:, 0]
    sound, ctl = [], []
    for i, r in enumerate(sample):
        lo = r["plen"] - 1
        rows = out[i, lo:lo + r["n"]]
        sound.append(gaps(rows, r["tokens"]))
        if control:
            ctl.append(gaps(rows, np.argmax(low[i, lo:lo + r["n"]], -1)))
    return sound, (ctl if control else None)


def gap_numbers(gaps: list) -> dict[str, float]:
    """The compared number: the mean, over every served token of the
    sample, of the gap below the reference's best (PERF.md gives why the
    mean and not the widest gap)."""
    return {"logit_gap_mean": float(np.mean(np.concatenate(gaps)))}


def log_gaps(log, sample: list[dict], gaps: list, who: str) -> None:
    """Each compared request's mean gap and the first served position
    whose gap is above 0, so that a reading can be traced to its
    requests."""
    parts = []
    for r, g in zip(sample, gaps):
        above = np.flatnonzero(g > 0)
        first = int(above[0]) if above.size else -1
        parts.append(f"{r['rid']}:{r['n']}:{float(np.mean(g)):.4g}:{first}")
    log(f"gaps of the {who} by request (rid:served:mean:first above 0): "
        + " ".join(parts))


def run_cell(spec: dict, cell: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, do_trace: bool, t_process: float, log,
             control: bool = False) -> dict:
    import jax

    clk = time.perf_counter
    clock = CompileClock(jax)
    dev = jax.devices()[0]
    m, s = cfg["model"], cfg["serving"]
    traffic.check(mix, s["max_len"])
    peak = costs.peaks(dev.device_kind) if dev.platform == "tpu" else None

    block = files.load_block(cfg)
    t0 = clk()
    params = weights.build_program_params(block.params_fn(m), seed)
    log(f"weights: made on {dev.device_kind} in one jitted call, "
        f"{clk() - t0:.3f} s")
    from repro.kernels import tune
    tune.resolved.clear()
    eng = program.build_engine(cfg, params, seed)
    del params
    sched = eng.scheduler()
    log(f"resident weight bytes: {eng.resident_weight_bytes()}")
    log(f"resident cache bytes: {eng.resident_cache_bytes()['total']}")
    client = Client(sched, clk, spans=do_trace)
    compile_pass(client, mix, cfg, seed)
    routes = {f"{k}[{key}]": r for (k, key), r in sorted(tune.resolved.items())}
    for name, route in routes.items():
        log(f"route {name}: {route}")
    off = {n: r for n, r in routes.items() if r not in PALLAS_ROUTES}
    if dev.platform == "tpu" and off:
        log(f"warning: kernels off the Pallas routes: {off}")

    warm = float(mix["warmup_s"])
    arr = mix["arrivals"]
    target = s["slots"] * (1 + arr.get("depth_per_slot", 0))
    warm_reqs = _phase_reqs(mix, m["vocab"], seed, 0, warm, s["slots"])
    win_reqs = _phase_reqs(mix, m["vocab"], seed, 1, seconds, s["slots"])
    t_warm = clk()
    if arr["kind"] == "poisson":
        client.run_poisson(warm_reqs, t_warm, t_warm + warm, 0)
    else:
        client.run_backlog(warm_reqs, target, t_warm + warm, 0)
    comp_setup = clock.read()
    w0 = clk()
    setup_s = w0 - t_process
    snaps = {"start": probe.snapshot(sched, w0)}
    outstanding_start = client.outstanding()
    w1 = w0 + seconds
    tdir = None
    if do_trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        t_a = w0 + TRACE_AT * seconds
        t_b = min(t_a + TRACE_S, w1)

        def start_trace():
            snaps["trace_start"] = probe.snapshot(sched, clk())
            jax.profiler.start_trace(tdir)
            snaps["trace_t0"] = clk()

        def stop_trace():
            snaps["trace_t1"] = clk()
            snaps["trace_stop"] = probe.snapshot(sched, snaps["trace_t1"])
            jax.profiler.stop_trace()
        client.hooks = [(t_a, start_trace), (t_b, stop_trace)]
    if arr["kind"] == "poisson":
        client.run_poisson(win_reqs, w0, w1, 1)
    else:
        client.run_backlog(win_reqs, target, w1, 1)
    snaps["end"] = probe.snapshot(sched, clk())
    comp_win = clock.read()
    facts = {"outstanding_start": outstanding_start,
             "outstanding_end": client.outstanding()}
    due_win = [r for r in client.records.values() if r["phase"] == 1]
    if arr["kind"] == "poisson":
        # the same traffic goes on past the window's end until every
        # request due in the window is delivered: its latency counts
        # the whole wait, and the load does not drop while it is served
        t_end = clk()
        client.run_poisson(
            _phase_reqs(mix, m["vocab"], seed, 2, DRAIN_S, s["slots"]),
            t_end, t_end + DRAIN_S, 2,
            until=lambda: all(r["done"] is not None for r in due_win))
    t_stop = clk()
    recs = list(client.records.values())
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    slots = s["slots"]

    # ------------------------------------------------------------ metrics
    lat = stats.latencies(recs, w0, w1, t_stop)
    waits = stats.first_token_waits(recs, w0, w1, t_stop)
    tpot = stats.per_token(recs, w0, w1)
    in_win = [r for r in recs if r["done"] is not None and w0 <= r["done"] < w1]
    failed = [r for r in recs if r["done"] is not None
              and (r["status"] != "completed" or r["tokens"].size != r["n"])]
    lags = [r["submit"] - r["due"] for r in due_win]
    reqs_by_rid = {r["rid"]: {"plen": r["plen"], "n": r["n"],
                              **({"cached": r["cached"]} if r["done"] else {})}
                   for r in recs}
    delivered = {r["rid"] for r in in_win}
    work = probe.window_work(snaps["start"], snaps["end"], reqs_by_rid,
                             delivered, s["prefill_chunk"])
    facts.update(due=len(due_win), delivered_in_window=len(in_win),
                 undelivered=sum(r["done"] is None for r in due_win),
                 served_past_end_s=t_stop - w1,
                 decode_steps_per_s=work.steps / (w1 - w0))
    # what a metric reader may read (metrics/<name>.py: read(ctx))
    ctx = {
        "cell": cell, "config": cfg, "mix": mix, "slots": slots,
        "window_s": w1 - w0, "records": recs, "window": (w0, w1),
        "latency": lat, "first_token": waits, "per_token": tpot,
        "submit_lag": lags, "snapshots": snaps, "work": work,
        "work_cost": block.work_of(m, work), "peak": peak, "trace": None,
    }
    log(f"window: {w1 - w0:.3f} s, requests due {len(due_win)}, delivered "
        f"in it {len(in_win)}, decode steps {work.steps}, tokens decoded "
        f"{work.decoded}, chunks {work.chunks}")
    log(f"served past the window's end: {t_stop - w1:.3f} s, requests due "
        f"in the window still undelivered {facts['undelivered']}")
    log(f"samples: latency {len(lat)}, first-token {len(waits)}, per-token "
        f"{len(tpot)}")
    if lags:
        log(f"submit lateness: p50 {1e3 * stats.percentile(lags, 50):.3f} ms,"
            f" p90 {1e3 * stats.percentile(lags, 90):.3f} ms, max "
            f"{1e3 * max(lags):.3f} ms")
    log(f"compile: set-up {comp_setup[0]:.3f} s over {comp_setup[1]} "
        f"compiles, {comp_setup[2]} persistent-cache hits; in the window "
        f"{comp_win[0] - comp_setup[0]:.3f} s over "
        f"{comp_win[1] - comp_setup[1]} compiles, "
        f"{comp_win[2] - comp_setup[2]} cache hits")
    log(f"peak_bytes_in_use: {peak_bytes}; allocator: {mem}")

    if do_trace:
        ctx["trace"] = trace.reduce_dir(tdir, snaps["trace_t1"]
                                        - snaps["trace_t0"])
        shutil.rmtree(tdir, ignore_errors=True)
        tw = probe.window_work(snaps["trace_start"], snaps["trace_stop"],
                               reqs_by_rid, {r["rid"] for r in recs
                                             if r["done"] is not None and
                                             snaps["trace_t0"] <= r["done"]
                                             < snaps["trace_t1"]},
                               s["prefill_chunk"])
        ctx["trace_work"] = block.work_of(m, tw)

    metrics = {}
    section = "per_layer" if do_trace else "end_to_end"
    for spec_m in files.metrics_for(spec, cell["name"], section):
        name = spec_m["name"]
        if name == "setup_s":
            value = setup_s
        else:
            value = files.load_module("metrics", name).read(ctx)
        if value is None or (isinstance(value, float) and math.isnan(value)):
            if section == "end_to_end":
                raise RuntimeError(f"end-to-end metric {name}: nothing to "
                                   f"read in this run")
            log(f"metric {name}: nothing to read")
            continue
        metrics[name] = {"value": float(value), "unit": spec_m["unit"]}
    if ctx.get("bounds"):
        log(f"roofline least time set by: {ctx['bounds']}")

    # ------------------------------------------------------------- check
    del client.sched, sched, eng
    gc.collect()
    limits = cfg["check"]
    want = limits["checked_requests"]
    sample = _sample([r for r in recs if r["done"] is not None
                      and r["done"] >= w0 and r["status"] == "completed"
                      and r["tokens"].size == r["n"]], seed, want)
    compared = {"checked_requests": (len(sample), want)}
    if sample:
        t_ref = clk()
        gaps, ctl_gaps = reference_gaps(cfg, seed, sample, control)
        log(f"reference: {len(sample)} requests, "
            f"{sum(r['n'] for r in sample)} served tokens, "
            f"{clk() - t_ref:.3f} s")
        log_gaps(log, sample, gaps, "program")
        if control:
            log_gaps(log, sample, ctl_gaps, "control")
        numbers = gap_numbers(gaps)
        if control:
            log(f"program's numbers (the control takes its place below): "
                f"{numbers}")
            numbers = gap_numbers(ctl_gaps)
        for name, value in numbers.items():
            compared[name] = (value, limits[name])
    compared["failed_requests"] = (len(failed), 0)
    correct = (len(sample) == want and
               all(v <= lim for k, (v, lim) in compared.items()
                   if k != "checked_requests"))
    result = {
        "correct": correct, "attempted": len(due_win), "failed": len(failed),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak_bytes},
        "window": facts,
    }
    if do_trace and ctx["trace"] is not None:
        tr = ctx["trace"]
        result["device"]["busy_s"] = tr["busy_s"]
        result["device"]["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["top_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    return result
