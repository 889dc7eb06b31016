"""Data-parallel replica serving: one request queue, R per-device engines.

The mesh scheduler (serving.scheduler with `mesh=`) shards one slot batch
over devices; this module is the other axis of scale-out: full model
replicas, each a single-device `ServingEngine` with its own scheduler,
fed round-robin from one submission queue. Replicas share nothing at
runtime — no collectives, no cross-device sync — so R replicas multiply
request throughput by R as long as each fits its device.

That fit is the paper's deployment argument in device units: packed 1-bit
weights are ~32x smaller than their fp32 masters, so the weight budget
that forces a float deployment to *partition* across 8 devices fits a
*whole replica* on 1 (`devices_needed` measures it from real resident
bytes; benchmarks/bench_sharded_serving.py records it). Replicas are the
better trade whenever the model fits: tensor parallelism buys latency at
the cost of per-layer collectives, replicas buy throughput for free.

Each replica's params/cache/state are committed to its own device
(construction runs under `jax.default_device`), and `generate` drives
every replica's scheduler from its own Python thread — the GIL is
released inside `block_until_ready`, so host-side scheduling of replica
i overlaps device compute of replica j even on one process.

Greedy outputs are bit-identical to a single-device engine serving the
same requests (per-row compute is batch-composition-independent — the
scheduler's invariant), so replica fan-out is invisible in tokens.
Sampled requests draw from per-replica key streams: deterministic given
the replica assignment (round-robin by submission order), but not the
same draws a single engine would make.

Failure is a first-class input (serving.faults): each replica worker
drives its scheduler through `_drive`, which consults the server's
`FaultPlan` at site `replica<i>` once per poll — an armed 'death' fault
raises `ReplicaDead` carrying the completions harvested so far. `serve`
tracks per-replica health, propagates every worker exception (nothing is
swallowed into a silent partial result), and fails over: a dead
replica's UNFINISHED requests are resubmitted round-robin to the
surviving replicas after an exponential backoff, for up to
`failover_rounds` extra rounds. Because greedy per-row compute is
batch-composition-independent, the failed-over tokens are bit-identical
to a fault-free run. Only when every replica is dead (or rounds are
exhausted) does `serve` raise `ReplicaDead`, with the completions it did
collect attached as `.partial`.
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.packed import params_frozen
from repro.models.api import get_model
from repro.serving.engine import Request, ServingEngine
from repro.serving.faults import FaultPlan, ReplicaDead

__all__ = ["ReplicaServer", "devices_needed"]


def devices_needed(resident_bytes: int, device_budget_bytes: int) -> int:
    """Devices a tenant of `resident_bytes` needs under a per-device
    memory budget — the unit the 32x packed shrink is spent in."""
    assert device_budget_bytes > 0
    return max(1, -(-int(resident_bytes) // int(device_budget_bytes)))


class ReplicaServer:
    """R single-device serving engines behind one queue.

    `devices`: one jax device per replica (default: every visible
    device). Engine kwargs (`freeze`, `kv_bits`, `slots`, `prefill_chunk`,
    `page_size`, ...) apply to every replica. With `freeze`, fp masters
    are packed once, where they already live, and only the packed tree is
    placed on the replicas' devices (masters are ~32x larger: a full-width
    copy per device would not fit). Each replica holds its own copy of the
    params (device_put at construction), its own KV cache/pool, and its
    own prefix tree — prefix
    sharing stays per-replica, which is why round-robin (not
    least-loaded) assignment is the default: equal interleaving keeps
    repeated prefixes landing on every replica.
    """

    def __init__(self, cfg: ModelConfig, params, *, devices=None,
                 fault_plan: FaultPlan | None = None,
                 failover_rounds: int = 2, backoff_s: float = 0.01,
                 **engine_kw):
        self.devices = (list(devices) if devices is not None
                        else list(jax.devices()))
        assert self.devices, "no devices for replicas"
        assert "mesh" not in engine_kw, \
            "replicas are single-device engines — use ServingEngine(mesh=) " \
            "for sharded serving (or mesh-shard each replica externally)"
        self.fault_plan = fault_plan
        self.failover_rounds = failover_rounds
        self.backoff_s = backoff_s
        self.health = [True] * len(self.devices)
        self.last_errors: dict[int, str] = {}
        self.failovers = 0
        self.engines: list[ServingEngine] = []
        if engine_kw.get("freeze") and not params_frozen(params):
            params = get_model(cfg).freeze(params)
        for dev in self.devices:
            with jax.default_device(dev):
                self.engines.append(
                    ServingEngine(cfg, jax.device_put(params, dev),
                                  **engine_kw))

    @property
    def n_replicas(self) -> int:
        return len(self.engines)

    def _shards(self, requests: list[Request]) -> list[list[Request]]:
        return [requests[i::self.n_replicas] for i in range(self.n_replicas)]

    def _drive(self, i: int, shard: list[Request], key) -> list:
        """Drive replica i's scheduler over its request shard, consulting
        the fault plan at site `replica<i>` once per poll. Returns the
        shard's completions in order; an armed 'death' fault raises
        ReplicaDead whose `.partial` maps shard position -> Completion
        for requests that already finished — failover resubmits only the
        remainder."""
        eng = self.engines[i]
        with jax.default_device(self.devices[i]):
            sched = eng.scheduler()
            sched.reseed(key if key is not None else eng._next_key())
            pos = {sched.submit(r): j for j, r in enumerate(shard)}
            done: dict = {}
            while len(done) < len(shard):
                if self.fault_plan is not None:
                    for f in self.fault_plan.tick(f"replica{i}"):
                        if f.kind == "death":
                            raise ReplicaDead(
                                f"replica {i} ({self.devices[i]}) died "
                                f"(injected fault)", partial=done)
                for c in sched.poll(drain=True):
                    if c.rid in pos:
                        done[pos[c.rid]] = c
        return [done[j] for j in range(len(shard))]

    def serve(self, requests: list[Request], key=None) -> list:
        """Serve `requests` across the healthy replicas (round-robin by
        index), one scheduler thread per replica; returns the full
        `Completion` objects in request order.

        Fault tolerance: a worker that raises ReplicaDead is marked
        unhealthy, its already-finished completions are kept, and its
        unfinished requests are resubmitted round-robin to the survivors
        after an exponential backoff — up to `failover_rounds` extra
        rounds. Greedy failed-over tokens are bit-identical to a
        fault-free run (per-row compute is batch-composition-
        independent). Any OTHER worker exception is re-raised here on
        the caller's thread — never swallowed into a partial result.
        With no survivors or rounds exhausted, raises ReplicaDead with
        everything collected so far in `.partial`."""
        assert requests, "empty batch"
        results: dict = {}
        remaining = list(range(len(requests)))
        for attempt in range(self.failover_rounds + 1):
            alive = [i for i, h in enumerate(self.health) if h]
            if not alive:
                break
            shards = {r: remaining[j::len(alive)]
                      for j, r in enumerate(alive)}
            outs: dict = {}
            errs: dict = {}

            def work(i: int) -> None:
                try:
                    if shards[i]:
                        outs[i] = self._drive(
                            i, [requests[g] for g in shards[i]], key)
                except BaseException as e:   # inspected on caller's thread
                    errs[i] = e

            threads = [threading.Thread(target=work, args=(i,), daemon=True)
                       for i in alive]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, e in errs.items():
                if not isinstance(e, ReplicaDead):
                    raise e              # real bug: propagate, don't fail over
            still: list[int] = []
            for i in alive:
                if not shards[i]:
                    continue
                if i in errs:
                    self.health[i] = False
                    self.last_errors[i] = str(errs[i])
                    partial = errs[i].partial
                    for j, g in enumerate(shards[i]):
                        if j in partial:
                            results[g] = partial[j]
                        else:
                            still.append(g)
                else:
                    for j, g in enumerate(shards[i]):
                        results[g] = outs[i][j]
            remaining = sorted(still)
            if not remaining:
                return [results[g] for g in range(len(requests))]
            self.failovers += 1
            time.sleep(self.backoff_s * (2 ** attempt))
        raise ReplicaDead(
            f"{len(remaining)} request(s) unserved after "
            f"{self.failovers} failover round(s): "
            f"{sum(self.health)}/{self.n_replicas} replicas healthy",
            partial=results)

    def generate(self, requests: list[Request], key=None
                 ) -> list[np.ndarray]:
        """Serve `requests` across every replica (round-robin by index),
        one scheduler thread per replica; returns token arrays in request
        order. Tokens-only shim over `serve` — failover and worker-
        exception propagation included."""
        return [c.tokens for c in self.serve(requests, key=key)]

    def stats(self) -> dict:
        """Aggregate + per-replica serving stats, resident bytes, and
        health: which replicas are alive, the recorded reason each dead
        one died (`last_errors`), and how many failover rounds ran."""
        per = []
        for i, (dev, eng) in enumerate(zip(self.devices, self.engines)):
            wb = eng.resident_weight_bytes()
            entry = {"device": str(dev), "healthy": self.health[i],
                     "weight_bytes": wb["binary"] + wb["other"],
                     "cache_bytes": eng.resident_cache_bytes()["total"]}
            if i in self.last_errors:
                entry["error"] = self.last_errors[i]
            if eng._sched is not None:
                entry["scheduler"] = dict(eng._sched.stats)
            per.append(entry)
        tokens = sum(e.get("scheduler", {}).get("tokens_out", 0)
                     for e in per)
        return {"replicas": self.n_replicas,
                "healthy": sum(self.health), "failovers": self.failovers,
                "tokens_out": tokens, "per_replica": per}
