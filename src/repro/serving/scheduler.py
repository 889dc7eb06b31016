"""Continuous-batching scheduler: fixed decode slots over a request queue.

Admission: with `prefill_chunk=None` (legacy) a pending request is
prefilled alone (batch 1) in one fused jit call and its KV-cache /
recurrent-state rows are written into a free slot of the shared batch
cache (`models.api.cache_batch_axes` finds the batch axis of every cache
leaf structurally, so the same insertion works for dense, MoE, audio,
VLM, SSM and hybrid families — for the recurrent families the row
overwrite IS the per-slot state reset). This covers the bit-resident
cache too: with kv_bits=1 the K/V leaves are plain uint32 bitplane
arrays (plus fp32 per-head V-scale leaves), each with an ordinary batch
axis, so slot insertion and recycling need no special casing. Its first
token is sampled from the prefill logits on device.

Chunked admission (`prefill_chunk=C`): the prompt advances through the
slot cache one fixed-shape (1, C) chunk at a time via the family's
`Model.prefill_chunk` — KV rows (packed bitplanes + running V scale when
kv_bits=1), recurrent conv/h states and the rg ring buffer all land
incrementally. Between chunks the scheduler runs a decode burst bounded
to `interleave_steps`, so admitting a long prompt no longer freezes
every in-flight slot for the whole prefill (time-to-first-token for the
new request trades against inter-token latency for the running ones),
and admission compiles once per chunk shape — never per prompt length.
At most one chunk advances between bursts. Rows mid-admission are marked
with a pos = -1 sentinel during bursts: every family's decode computes
but WRITES NOTHING for such rows, so an interleaved burst cannot corrupt
a partially prefilled slot (models.transformer / models.ssm_lm).

Decode: one jit'd step advances every slot together — per-slot position
vector, per-slot temperature, per-slot PRNG key — inside a
lax.while_loop that only returns control to the host when some slot
finishes (its own `max_new_tokens` budget or its `eos_id`) or, while an
admission is mid-flight, after `interleave_steps` steps. Output tokens
accumulate in a device buffer, so the host syncs once per completion
event, not once per token. A freed slot is recycled to the next queued
request immediately. All wall-time stats sync the device before reading
the clock (`prefill_s` / `decode_s` measure compute, not dispatch).

Ordering guarantees: completions are delivered in completion order;
requests that finish in the same burst are delivered in submission
order. Greedy outputs are batch-composition-independent — bit-identical
whether the request runs alone or in mixed traffic, whole-prompt or
chunked admission — for every family whose per-row compute is
independent; the one exception is MoE under expert-capacity pressure,
where capacity-based dispatch drops tokens by *batch-global* count
(models.common.moe_ffn), so slot neighbors can evict each other's expert
assignments exactly as they would in any capacity-routed server (and a
padded final chunk adds pad tokens to that same global count). Sampled
outputs (temperature > 0) are a deterministic replay of (base key,
submission index since the last reseed, token index) — the same
submissions after the same reseed reproduce the same draws regardless
of slot assignment.

Paged mode (`page_size=P`, attention families only): the slot cache's
K/V leaves become a batch-axis-free page pool `(layers, pool_pages, P,
Hkv, words)` plus per-slot int32 page tables (sentinel = pool_pages;
chunk writes scatter through the table with .set(mode="drop"), so the
pos=-1 burst sentinel keeps working unchanged). Pages are refcounted
(serving.pager.PagePool) and pre-allocated at admission for the
request's worst case. With `prefix_cache=True` a radix tree over
retired immutable full prompt pages (serving.prefix_cache.PrefixCache)
lets admission pin the longest cached full-page prefix zero-copy into
the new slot's table — prefill runs only for the unseen suffix, the
kv_bits=1 v_scale running mean is restored from a page-boundary
snapshot, and Completion.ttft charges only that suffix compute
(ttft_wall keeps the submit->first-token wall; cached_tokens counts the
pinned tokens). Retirement inserts the request's full prompt pages into
the tree; LRU unpinned leaves are evicted only when an admission needs
pages and the pool is full. Paging is a pure addressing change: outputs
are asserted token-identical to the contiguous slot cache, and
recurrent (SSM/hybrid) state stays unpaged — it is O(1) per slot.

Resilience (`serving.faults`): `submit` validates requests up front and
raises typed `RequestError`s instead of failing deep inside a jit; a
bounded admission queue (`queue_cap`) applies backpressure — `submit`
raises `QueueFull` (policy "reject") or serves until space frees
(policy "block"); requests whose `deadline_s` TTFT deadline already
passed are shed at admission, before they burn any prefill compute
(`Completion.status == "shed"`); a poison request — non-finite logits
(flagged per-row inside the jit), an injected admission fault, or a
page allocation that stays unsatisfiable after eviction retries —
retires alone with `status == "error"` while every other slot keeps
decoding bit-identically (per-row compute is independent; the poison
only ever touched its own logits). Decode bursts consult an injectable
`FaultPlan` and retry transient device errors with exponential backoff
(the fault fires before the jit call, so the retried burst is
bit-identical); an invariant watchdog audits the page pool + prefix
tree + cross-layer refcounts at burst boundaries under
`REPRO_CHECK_INVARIANTS=1` (tests enable it globally) and degrades a
corrupted prefix tree to cache-bypass rather than crashing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels import tune
from repro.models.api import Model, PAGED, cache_batch_axes
from repro.serving.faults import (FaultPlan, InvariantViolation, QueueFull,
                                  RequestError, TransientDeviceError)
from repro.serving.pager import PagePool
from repro.serving.prefix_cache import PrefixCache
from repro.serving.sampling import request_key, sample_tokens, step_keys

Array = jax.Array


@dataclasses.dataclass
class Request:
    prompt: np.ndarray           # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0     # 0 => greedy
    eos_id: int | None = None    # stop early when this token is sampled
    img_emb: np.ndarray | None = None   # vlm only: (n_img_tokens, d_vision)
    # TTFT deadline in seconds from submit: a request still queued when it
    # expires is shed at admission instead of burning prefill compute
    deadline_s: float | None = None
    priority: int = 0            # higher admits first; ties go by rid (FIFO)


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: np.ndarray           # includes the eos token, if one was sampled
    # seconds, submit -> harvest. Granularity is the completion *event*:
    # requests finishing inside the same burst share a timestamp, so under
    # run()'s drain tail this is an upper bound on true latency
    latency: float
    # seconds of device compute the request's OWN admission cost, through
    # first-token sampling (device-synced, like prefill_s). On a prefix-
    # cache hit only the unseen suffix prefills, so the skipped prefix is
    # never charged here — the number the prefix cache exists to shrink
    ttft: float = 0.0
    # seconds, submit -> first token sampled, wall clock: admission compute
    # PLUS every stall behind other slots' chunks and decode bursts
    ttft_wall: float = 0.0
    # prompt tokens served from the prefix cache (skipped prefill)
    cached_tokens: int = 0
    # inter-token intervals (seconds) for decode tokens, at burst
    # granularity: a burst's n tokens split the burst duration evenly and
    # time the slot spent stalled BEFORE the burst (behind another
    # request's admission) lands on its first token's interval — exactly
    # the head-of-line blocking the interleave benchmark asserts on
    itl: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros((0,)))
    # how this rid resolved — every submitted rid resolves to EXACTLY one
    # of: "completed" (served its tokens), "shed" (TTFT deadline passed
    # before admission; no compute spent), "error" (poisoned: non-finite
    # logits, an injected admission fault, or unsatisfiable page alloc)
    status: str = "completed"
    error: str | None = None     # human-readable cause when status=="error"


@dataclasses.dataclass
class _Running:
    rid: int
    prompt_len: int
    max_new: int
    prompt: np.ndarray | None = None   # kept only for prefix-tree insertion


@dataclasses.dataclass
class _Admission:
    """One request mid-chunked-admission: its slot is reserved (neither
    free nor running) and its prompt advances one chunk per poll."""
    slot: int
    rid: int
    req: Request
    n_chunks: int
    next: int = 0
    start: int = 0      # prompt tokens served from the prefix cache
    poison: float = 0.0  # injected NaN added to first-token logits


class Scheduler:
    """Admits requests from a queue into `n_slots` decode slots.

    submit(request) -> rid; poll() runs one admit/decode/harvest round
    and returns the newly completed requests; run() polls until idle and
    returns {rid: Completion} for everything that completed during it.
    Completions are handed to the caller, not retained — scheduler state
    stays bounded no matter how long it serves.

    prefill_chunk: None = whole-prompt admission (one compile per
    prompt-length bucket); C > 0 = chunked admission (one compile per
    chunk *shape*, bounded regardless of traffic — see
    `prefill_shape_count`). interleave_steps bounds how long a decode
    burst runs while an admission is mid-flight.
    """

    def __init__(self, cfg: ModelConfig, model: Model, params, *,
                 n_slots: int = 4, max_len: int = 512,
                 key: Array | None = None, prefill_chunk: int | None = None,
                 interleave_steps: int = 8, page_size: int | None = None,
                 pool_pages: int | None = None, prefix_cache: bool = False,
                 mesh=None, queue_cap: int | None = None,
                 overflow: str = "reject",
                 fault_plan: FaultPlan | None = None,
                 check_invariants: bool | None = None,
                 burst_retries: int = 3, backoff_s: float = 0.01):
        assert prefill_chunk is None or prefill_chunk >= 1
        assert overflow in ("reject", "block"), overflow
        assert queue_cap is None or queue_cap >= 1
        self.cfg, self.model, self.params = cfg, model, params
        self.n_slots, self.max_len = n_slots, max_len
        self.max_out = max_len
        self.prefill_chunk = prefill_chunk
        self.interleave_steps = interleave_steps
        self.queue_cap, self.overflow = queue_cap, overflow
        self._faults = fault_plan
        self.burst_retries, self.backoff_s = burst_retries, backoff_s
        # invariant watchdog: explicit arg wins; default to the env knob
        # (tests/conftest.py sets REPRO_CHECK_INVARIANTS=1 globally)
        self._check_inv = (check_invariants if check_invariants is not None
                           else os.environ.get("REPRO_CHECK_INVARIANTS") == "1")
        self.last_violations: list[str] = []
        self._done_buf: list[Completion] = []   # completions harvested
        # inside a blocking submit, delivered by the next poll()
        # paged KV applies to the attention families only — mamba/rg
        # recurrent state is O(1) per slot and stays slot-resident
        attn_fam = cfg.family in ("dense", "moe", "audio", "vlm")
        self._paged = page_size is not None and attn_fam
        cache_kw = {}
        if self._paged:
            assert page_size >= 1
            assert prefill_chunk is not None, \
                "paged KV fills through chunked admission — pass prefill_chunk"
            self.page_size = page_size
            self.n_pages = -(-max_len // page_size)
            self.pool_pages = (pool_pages if pool_pages is not None
                               else n_slots * self.n_pages)
            cache_kw = {"page_size": page_size,
                        "pool_pages": self.pool_pages}
            self._pager = PagePool(self.pool_pages, fault_plan=fault_plan)
            self._slot_pages: dict[int, list[int]] = {}
        # the prefix tree shares full pages across requests with equal
        # token prefixes; vlm is excluded — its image embeddings condition
        # every KV row, so equal token prefixes do NOT imply equal pages
        # (the self-KV pools are still paged, just never shared)
        self._use_tree = bool(prefix_cache) and self._paged and \
            cfg.family != "vlm"
        if prefix_cache:
            assert self._paged or not attn_fam, \
                "prefix_cache needs the paged cache — pass page_size"
        if self._use_tree:
            # running V-scale snapshots are taken at chunk ends, so page
            # boundaries must land on chunk ends to be insertable
            assert cfg.kv_bits != 1 or page_size % prefill_chunk == 0, \
                f"prefix_cache with kv_bits=1 needs page_size divisible " \
                f"by prefill_chunk ({page_size} % {prefill_chunk})"
            self._ptree = PrefixCache(self._pager, page_size)
        self._needs_vs = cfg.kv_bits == 1 and attn_fam
        self._axes = cache_batch_axes(model, max_len, **cache_kw)
        self._base_key = key if key is not None else jax.random.PRNGKey(0)
        self._key_rid0 = 0      # rid the current base key was set at
        self._next_rid = 0
        self._queue: deque[tuple[int, Request]] = deque()
        self._free = list(range(n_slots))
        self._running: dict[int, _Running] = {}
        self._admitting: deque[_Admission] = deque()
        self._submit_time: dict[int, float] = {}    # pending/running only
        self._ttft: dict[int, float] = {}
        self._ttft_wall: dict[int, float] = {}
        self._req_prefill_s: dict[int, float] = {}  # own-admission compute
        self._cached_tokens: dict[int, int] = {}
        self._vs_snaps: dict[int, dict[int, Any]] = {}
        self._itl: dict[int, list] = {}
        self._slot_last_tok: dict[int, float] = {}
        self._prev_out_len = np.zeros((n_slots,), np.int64)
        self._prefill_shapes: set = set()
        self.stats = {"prefill_tokens": 0, "prefill_s": 0.0, "bursts": 0,
                      "decode_s": 0.0, "tokens_out": 0, "completed": 0,
                      "max_admit_stall_tokens": 0,
                      "prefill_tokens_saved": 0, "prefix_hits": 0,
                      "shed": 0, "errors": 0, "rejected": 0,
                      "burst_retries": 0, "invariant_violations": 0}

        self._cache = model.init_cache(n_slots, max_len, **cache_kw)
        self._state = {
            "cur": jnp.zeros((n_slots,), jnp.int32),
            "pos": jnp.zeros((n_slots,), jnp.int32),
            "active": jnp.zeros((n_slots,), bool),
            "out_len": jnp.zeros((n_slots,), jnp.int32),
            "budget": jnp.ones((n_slots,), jnp.int32),
            "temp": jnp.zeros((n_slots,), jnp.float32),
            "eos": jnp.full((n_slots,), -1, jnp.int32),
            "rkey": jnp.zeros((n_slots, 2), jnp.uint32),
            "outs": jnp.zeros((n_slots, self.max_out), jnp.int32),
            "done": jnp.zeros((n_slots,), bool),
            # poison flag: row produced non-finite logits (computed inside
            # the jit — one isfinite reduction over logits the step already
            # holds); a flagged row finishes immediately and harvests as
            # status="error" while its neighbors are untouched
            "err": jnp.zeros((n_slots,), bool),
            # per-slot so the state tree shards uniformly on axis 0; all
            # rows of one device tick together, decode_steps() takes max
            "steps": jnp.zeros((n_slots,), jnp.int32),
        }
        self._pkw = ({"max_len": max_len}
                     if cfg.family in ("dense", "moe", "audio", "vlm") else {})
        self._mesh = mesh
        self._dp = 1
        self._state_sh = self._cache_sh = None
        if mesh is not None:
            self._init_mesh(mesh)
        out_sh = (None if mesh is None
                  else (self._state_sh, self._cache_sh))
        self._admit_jit = jax.jit(
            lambda p, st, c, t, slot, rkey, b, tp, e, po: self._admit_impl(
                p, st, c, t, slot, rkey, b, tp, e, None, po),
            donate_argnums=(1, 2), out_shardings=out_sh)
        self._admit_img_jit = jax.jit(
            lambda p, st, c, t, img, slot, rkey, b, tp, e, po:
            self._admit_impl(p, st, c, t, slot, rkey, b, tp, e, img, po),
            donate_argnums=(1, 2), out_shardings=out_sh)
        self._burst = jax.jit(self._burst_impl, donate_argnums=(1, 2),
                              static_argnums=(3, 4))
        self._burst_jits: dict[tuple[bool, int], Any] = {}
        self._chunk_jits: dict[tuple[bool, bool], Any] = {}

    # -- mesh placement -----------------------------------------------------
    def _init_mesh(self, mesh) -> None:
        """Data-parallel slot sharding: every state leaf and every cache
        leaf with a batch axis splits its slots over the mesh's 'data'
        axis; paged pool leaves (no batch axis — addressed through the
        batch-sharded page table) and the params replicate. Decode bursts
        run as a shard_map'ed per-device loop (`_sharded_burst`);
        admission jits stay global-GSPMD with the packed kernels pinned
        to their partitionable 'xla' route (`tune.gspmd_safe`). Any
        'model' axis in the mesh is left unreferenced by the serving
        state — leaves replicate across it, and tensor parallelism enters
        through the kernels.sharded wrappers instead."""
        assert "data" in mesh.axis_names, \
            f"serving mesh needs a 'data' axis, got {mesh.axis_names}"
        self._dp = int(mesh.shape["data"])
        assert self.n_slots % self._dp == 0, \
            f"n_slots={self.n_slots} must divide the data axis ({self._dp})"

        def cspec(leaf, ax):
            spec = [None] * leaf.ndim
            if ax != PAGED:                      # PAGED pools replicate
                spec[ax] = "data"
            return P(*spec)

        self._state_specs = jax.tree.map(
            lambda x: P(*(("data",) + (None,) * (x.ndim - 1))), self._state)
        self._cache_specs = jax.tree.map(cspec, self._cache, self._axes)
        self._state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                      self._state_specs)
        self._cache_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                      self._cache_specs)
        self._state = jax.device_put(self._state, self._state_sh)
        self._cache = jax.device_put(self._cache, self._cache_sh)
        self.params = jax.device_put(
            self.params, NamedSharding(mesh, P()))

    def _admit_ctx(self):
        """Trace-time kernel-route pin for the GSPMD admission path (a
        no-op without a mesh)."""
        return (tune.gspmd_safe() if self._mesh is not None
                else contextlib.nullcontext())

    def _sharded_burst(self, drain: bool, max_steps: int):
        """shard_map'ed decode burst: each device loops over its own slot
        shard — per-row positions, sampling state and page-table gathers
        all read local rows, so the loop body is exactly the single-device
        one on a n_slots/D batch. Loop trip counts may diverge across
        devices (each stops at its own completion event); that moves burst
        *boundaries*, never tokens, because rows are independent. Paged
        pool leaves are replicated inputs that each device writes at
        disjoint rows (its own slots' pages); their replicas are re-merged
        after the loop by an exact masked psum — changed entries are
        summed across devices (exactly one device contributes each one)
        and unchanged entries keep the old value bit-for-bit."""
        fn = self._burst_jits.get((drain, max_steps))
        if fn is None:
            def body(params, state, cache):
                cin = cache
                state, cache = self._burst_impl(params, state, cache,
                                                drain, max_steps)
                if self._dp > 1:
                    def merge(old, new, ax):
                        if ax != PAGED:
                            return new           # batch-sharded leaf
                        chg = new != old
                        tot = jax.lax.psum(
                            jnp.where(chg, new, jnp.zeros((), new.dtype)),
                            "data")
                        anyc = jax.lax.psum(chg.astype(jnp.int32), "data") > 0
                        return jnp.where(anyc, tot, old)
                    cache = jax.tree.map(merge, cin, cache, self._axes)
                return state, cache

            pspecs = jax.tree.map(lambda _: P(), self.params)
            fn = jax.jit(jax.shard_map(
                body, mesh=self._mesh,
                in_specs=(pspecs, self._state_specs, self._cache_specs),
                out_specs=(self._state_specs, self._cache_specs),
                check_vma=False), donate_argnums=(1, 2))
            self._burst_jits[(drain, max_steps)] = fn
        return fn

    # -- device-side pieces -------------------------------------------------
    def _admit_impl(self, params, state, cache, tokens, slot, rkey,
                    budget, temp, eos, img, poison):
        """Prefill one request (batch 1), write its cache/state rows into
        `slot`, and sample its first token — one fused jit call per
        admission. Scalars are traced, so admission compiles once per
        prompt-length bucket and never per value. `poison` is a traced
        scalar added to the first-token logits — 0.0 in normal operation
        (a no-op on the values), NaN when a fault plan poisons this
        admission, which trips the in-jit non-finite flag below."""
        kw = dict(self._pkw)
        if img is not None:
            kw["img_emb"] = img
        logits1, slot_cache = self.model.prefill(params, tokens, **kw)
        prompt_len = tokens.shape[1]
        cache = jax.tree.map(
            lambda c, s, ax: jax.lax.dynamic_update_slice_in_dim(
                c, s.astype(c.dtype), slot, axis=ax),
            cache, slot_cache, self._axes)
        return self._first_token(state, cache, logits1, slot, prompt_len,
                                 rkey, budget, temp, eos, poison)

    def _chunk_final_impl(self, params, state, cache, tokens, slot, pos,
                          n_valid, rkey, budget, temp, eos, img, poison):
        """Last chunk of a chunked admission: advance the slot cache by the
        chunk, then sample the first token and arm the slot's decode state
        — the chunked twin of `_admit_impl`'s tail."""
        kw = {"img_emb": img} if img is not None else {}
        logits1, cache = self.model.prefill_chunk(params, tokens, cache,
                                                  slot, pos, n_valid, **kw)
        return self._first_token(state, cache, logits1, slot, pos + n_valid,
                                 rkey, budget, temp, eos, poison)

    def _first_token(self, state, cache, logits1, slot, prompt_len, rkey,
                     budget, temp, eos, poison=0.0):
        logits1 = logits1 + jnp.asarray(poison, jnp.float32)
        temp = jnp.asarray(temp, jnp.float32)
        tok = sample_tokens(logits1, jax.random.fold_in(rkey, 0)[None],
                            temp[None])[0]
        # a poisoned first token (non-finite logits: model pathology or an
        # injected NaN) finishes the slot immediately with the err flag set
        bad = ~jnp.isfinite(logits1).all()
        finished = bad | (tok == eos) | (budget <= 1)
        state = {
            "cur": state["cur"].at[slot].set(tok),
            "pos": state["pos"].at[slot].set(prompt_len),
            "active": state["active"].at[slot].set(~finished),
            "out_len": state["out_len"].at[slot].set(1),
            "budget": state["budget"].at[slot].set(budget),
            "temp": state["temp"].at[slot].set(temp),
            "eos": state["eos"].at[slot].set(eos),
            "rkey": state["rkey"].at[slot].set(rkey),
            "outs": state["outs"].at[slot].set(0).at[slot, 0].set(tok),
            "done": state["done"].at[slot].set(finished),
            "err": state["err"].at[slot].set(bad),
            "steps": state["steps"],
        }
        return state, cache

    def _burst_impl(self, params, state, cache, drain=False, max_steps=0):
        """Decode every slot until some slot completes (or none is active).
        The host only sees the loop's final state: one sync per completion
        event, never per token. With `drain` (queue empty: a freed slot
        has nothing to recycle to), run until every slot completes — one
        sync for the whole tail. With `max_steps` > 0 (an admission is
        mid-flight), also yield after that many steps so the next prompt
        chunk can advance. Inactive rows decode with a pos = -1 sentinel:
        they compute garbage but write neither cache rows nor recurrent
        state, so partially admitted slots stay intact."""
        # row count from the traced state, NOT self.n_slots: under the
        # mesh's shard_map burst this body sees one device's slot shard
        rows = jnp.arange(state["cur"].shape[0])
        start = state["steps"]

        def cond(carry):
            st, _ = carry
            go = jnp.any(st["active"])
            if not drain:
                go &= ~jnp.any(st["done"])
            if max_steps:
                go &= jnp.max(st["steps"] - start) < max_steps
            return go

        def body(carry):
            st, cache = carry
            act = st["active"]
            pos = jnp.where(act, st["pos"], -1)
            logits, cache = self.model.decode(params, st["cur"], cache, pos)
            keys = step_keys(st["rkey"], st["out_len"])
            nxt = sample_tokens(logits, keys, st["temp"])
            nxt = jnp.where(act, nxt, st["cur"])
            # per-row poison isolation: a row whose logits went non-finite
            # finishes NOW with err set; neighbors never see its values
            bad = act & ~jnp.isfinite(logits).all(axis=-1)
            # inactive rows write out of bounds -> dropped
            idx = jnp.where(act, st["out_len"], self.max_out)
            outs = st["outs"].at[rows, idx].set(nxt, mode="drop")
            out_len = st["out_len"] + act
            finished = act & (bad | (nxt == st["eos"])
                              | (out_len >= st["budget"]))
            st = dict(st, cur=nxt, pos=st["pos"] + act, active=act & ~finished,
                      out_len=out_len, outs=outs, done=st["done"] | finished,
                      err=st["err"] | bad, steps=st["steps"] + 1)
            return st, cache

        return jax.lax.while_loop(cond, body, (state, cache))

    # -- host-side loop -----------------------------------------------------
    def reseed(self, key: Array) -> None:
        """Set the base key for requests submitted from now on. Keys fold
        the request's index *since this reseed*, so replaying the same
        requests after the same reseed reproduces the same samples."""
        self._base_key = key
        self._key_rid0 = self._next_rid

    def _validate(self, req: Request) -> np.ndarray:
        """Reject a malformed request HERE, with a typed RequestError that
        names the problem — not ten frames deep in an admission jit with
        an opaque shape error. Returns the canonicalized int32 prompt."""
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1 or prompt.size < 1:
            raise RequestError(f"prompt must be a non-empty 1-D token "
                               f"array, got shape {prompt.shape}")
        if not np.issubdtype(prompt.dtype, np.integer):
            raise RequestError(f"prompt must hold integer token ids, got "
                               f"dtype {prompt.dtype}")
        lo, hi = int(prompt.min()), int(prompt.max())
        if lo < 0 or hi >= self.cfg.vocab:
            raise RequestError(f"prompt token ids must lie in "
                               f"[0, {self.cfg.vocab}), got [{lo}, {hi}]")
        if req.max_new_tokens < 1:
            raise RequestError(f"max_new_tokens must be >= 1, got "
                               f"{req.max_new_tokens}")
        if prompt.size + req.max_new_tokens > self.max_len:
            raise RequestError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds max_len={self.max_len}")
        if req.deadline_s is not None and req.deadline_s < 0:
            raise RequestError(f"deadline_s must be >= 0, got "
                               f"{req.deadline_s}")
        if self.cfg.family == "vlm":
            if req.img_emb is None:
                raise RequestError("vlm request needs img_emb")
            shape = np.asarray(req.img_emb).shape
            want = (self.cfg.n_img_tokens, self.cfg.d_vision)
            if shape != want:
                raise RequestError(f"img_emb shape {shape} != {want} "
                                   f"(n_img_tokens, d_vision)")
        elif req.img_emb is not None:
            raise RequestError(
                f"img_emb is vlm-only (family is {self.cfg.family!r})")
        if self._paged:
            need = -(-(int(prompt.size) + req.max_new_tokens - 1)
                     // self.page_size)
            if need > self.pool_pages:
                raise RequestError(f"request needs {need} pages > "
                                   f"pool_pages={self.pool_pages}")
        return prompt.astype(np.int32)

    def submit(self, req: Request) -> int:
        prompt = self._validate(req)
        if self.queue_cap is not None and len(self._queue) >= self.queue_cap:
            if self.overflow == "reject":
                self.stats["rejected"] += 1
                raise QueueFull(
                    f"admission queue at capacity ({self.queue_cap}); "
                    f"resubmit later or construct with overflow='block'")
            # "block" backpressure: serve until a queue slot frees. Any
            # completions harvested here are buffered and delivered by
            # the caller's next poll() — nothing is lost.
            while len(self._queue) >= self.queue_cap:
                self._done_buf.extend(self._poll_impl(False))
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append((rid, dataclasses.replace(req, prompt=prompt)))
        self._submit_time[rid] = time.time()
        return rid

    @property
    def idle(self) -> bool:
        return (not self._queue and not self._running
                and not self._admitting)

    @property
    def prefill_shape_count(self) -> int:
        """Distinct prefill shapes dispatched so far — an honest compile-
        count proxy (each distinct shape is one XLA compilation). Chunked
        admission is bounded by its chunk-shape variants; whole-prompt
        admission grows with every new prompt length."""
        return len(self._prefill_shapes)

    def _note_first_token(self, slot: int, rid: int) -> None:
        now = time.time()
        wall = now - self._submit_time[rid]
        self._ttft_wall[rid] = wall
        # ttft = the request's OWN admission compute (device-synced sum of
        # its prefill calls, first-token sampling included) — a prefix hit
        # skips the cached prefix entirely, so it is never charged here
        self._ttft[rid] = self._req_prefill_s.pop(rid, wall)
        self._slot_last_tok[slot] = now
        self._prev_out_len[slot] = 1

    def _admit(self, slot: int, rid: int, req: Request,
               poison: float = 0.0) -> None:
        if self._running:   # in-flight slots stall for this whole prefill
            self.stats["max_admit_stall_tokens"] = max(
                self.stats["max_admit_stall_tokens"], int(req.prompt.size))
        t0 = time.time()
        tokens = jax.device_put(req.prompt[None])
        rkey = request_key(self._base_key, rid - self._key_rid0)
        eos = -1 if req.eos_id is None else int(req.eos_id)
        if self.cfg.family == "vlm":
            assert req.img_emb is not None, "vlm request needs img_emb"
            img = jax.device_put(np.asarray(req.img_emb)[None])
            with self._admit_ctx():
                self._state, self._cache = self._admit_img_jit(
                    self.params, self._state, self._cache, tokens, img, slot,
                    rkey, req.max_new_tokens, float(req.temperature), eos,
                    poison)
        else:
            with self._admit_ctx():
                self._state, self._cache = self._admit_jit(
                    self.params, self._state, self._cache, tokens, slot,
                    rkey, req.max_new_tokens, float(req.temperature), eos,
                    poison)
        jax.block_until_ready(self._state["done"])   # honest prefill_s
        dt = time.time() - t0
        self.stats["prefill_s"] += dt
        self._req_prefill_s[rid] = dt
        self._prefill_shapes.add(("whole", int(req.prompt.size)))
        self._running[slot] = _Running(rid, int(req.prompt.size),
                                       req.max_new_tokens)
        self.stats["prefill_tokens"] += int(req.prompt.size)
        self._note_first_token(slot, rid)

    # -- chunked admission --------------------------------------------------
    def _chunk_call(self, final: bool, with_img: bool):
        """jit per (final, with_img) chunk variant — 2 shapes for most
        families, up to 4 for vlm. Mid chunks return only the cache, so
        the logits head is dead-code eliminated from their executable."""
        fn = self._chunk_jits.get((final, with_img))
        if fn is None:
            if final:
                def impl(p, st, c, t, slot, pos, nv, rkey, b, tp, e, po,
                         *img):
                    return self._chunk_final_impl(
                        p, st, c, t, slot, pos, nv, rkey, b, tp, e,
                        img[0] if img else None, po)
                fn = jax.jit(impl, donate_argnums=(1, 2),
                             out_shardings=(None if self._mesh is None else
                                            (self._state_sh, self._cache_sh)))
            else:
                def impl(p, c, t, slot, pos, nv, *img):
                    kw = {"img_emb": img[0]} if img else {}
                    return self.model.prefill_chunk(p, t, c, slot, pos, nv,
                                                    **kw)[1]
                fn = jax.jit(impl, donate_argnums=(1,),
                             out_shardings=(None if self._mesh is None else
                                            self._cache_sh))
            self._chunk_jits[(final, with_img)] = fn
        return fn

    # -- paged-cache plumbing -----------------------------------------------
    def _set_page_row(self, slot: int, pages: list[int]) -> None:
        """Write one slot's page-table row: `pages` in position order, the
        pool-size sentinel beyond (unallocated — kernels clip + mask)."""
        row = np.full((self.n_pages,), self.pool_pages, np.int32)
        row[:len(pages)] = pages
        self._cache["page_table"] = \
            self._cache["page_table"].at[slot].set(jnp.asarray(row))

    def _alloc_pages(self, n: int):
        """All-or-nothing page allocation, evicting cold prefix-tree
        entries when the free list alone cannot cover it."""
        got = self._pager.alloc(n)
        if got is None and self._use_tree:
            self._ptree.evict(n - self._pager.free_count())
            got = self._pager.alloc(n)
        return got

    def page_stats(self) -> dict | None:
        """Page-pool utilization split: allocated vs pinned-only-by-the-
        prefix-tree vs free, plus tree hit counters. None when unpaged."""
        if not self._paged:
            return None
        out = self._pager.stats()
        out["page_size"] = self.page_size
        out["pinned_by_prefix"] = self._ptree.n_pages if self._use_tree else 0
        if self._use_tree:
            out["prefix_tree"] = self._ptree.stats()
        return out

    def _retire_slot(self, slot: int, info: _Running,
                     ok: bool = True) -> None:
        """Release a completed slot's pages. With the prefix tree, its
        prompt-region FULL pages (immutable from here on — decode only
        ever wrote past the prompt) are offered to the tree first: new
        token runs hand their page's reference to the tree (zero-copy
        insertion), runs already cached keep the incumbent page and ours
        is released. Everything else — tail page, decode pages — drops
        its slot reference; pages still pinned by the tree or by other
        slots survive, the rest return to the free list. A slot retiring
        with status='error' (`ok=False`) never donates to the tree — its
        pages are suspect by definition."""
        pages = self._slot_pages.pop(slot)
        taken: set = set()
        if ok and self._use_tree and info.prompt is not None:
            ps = self.page_size
            snaps = self._vs_snaps.get(info.rid, {})
            n_full = info.prompt_len // ps
            payloads = []
            for i in range(n_full):
                if self._needs_vs and snaps.get((i + 1) * ps) is None:
                    break       # boundary missed its snapshot: stop here
                payloads.append(snaps.get((i + 1) * ps))
            taken = self._ptree.insert(info.prompt[:len(payloads) * ps],
                                       pages[:len(payloads)], payloads)
        self._vs_snaps.pop(info.rid, None)
        self._pager.decref([p for p in pages if p not in taken])
        self._set_page_row(slot, [])

    def _start_admission(self, slot: int, rid: int, req: Request,
                         poison: float = 0.0) -> bool:
        """Reserve `slot` and queue the request's chunked admission.
        Paged: allocate every page the request can reach up front (so
        decode never faults mid-flight), consulting the prefix tree first
        — matched full pages pin into the page table with zero copies and
        only the unseen suffix is scheduled for prefill. Returns False
        (nothing reserved) when the pool cannot satisfy the request even
        after evicting cold tree entries — the caller requeues."""
        c = self.prefill_chunk
        start = 0
        if self._paged:
            plen = int(req.prompt.size)
            ps = self.page_size
            pinned: list[int] = []
            payloads: list[Any] = []
            if self._use_tree:
                # cap the match below the full prompt: the final prompt
                # token must prefill HERE to produce first-token logits
                cap = ((plen - 1) // ps) * ps
                pinned, payloads = self._ptree.lookup(req.prompt[:cap])
                start = len(pinned) * ps
            need = -(-(plen + req.max_new_tokens - 1) // ps)
            fresh = self._alloc_pages(need - len(pinned))
            if fresh is None:
                if pinned:
                    self._pager.decref(pinned)
                return False
            pages = pinned + fresh
            self._slot_pages[slot] = pages
            self._set_page_row(slot, pages)
            if start:
                self.stats["prefix_hits"] += 1
                self.stats["prefill_tokens_saved"] += start
                self._cached_tokens[rid] = start
            # seed the boundary->v_scale snapshot map from the matched
            # payloads and restore the running mean at `start`, so suffix
            # prefill continues it exactly where the cached pages left off
            self._vs_snaps[rid] = {(i + 1) * ps: payloads[i]
                                   for i in range(len(payloads))}
            if start and self._needs_vs:
                self._cache["v_scale"] = self._cache["v_scale"].at[:, slot] \
                    .set(jnp.asarray(payloads[-1]))
        n_chunks = max(1, -(-(int(req.prompt.size) - start) // c))
        self._admitting.append(_Admission(slot, rid, req, n_chunks,
                                          start=start, poison=poison))
        return True

    def _advance_admission(self) -> None:
        """Advance the head admission by exactly one chunk."""
        adm = self._admitting[0]
        req, slot, c = adm.req, adm.slot, self.prefill_chunk
        lo = adm.start + adm.next * c
        n_valid = min(c, int(req.prompt.size) - lo)
        final = adm.next == adm.n_chunks - 1
        with_img = self.cfg.family == "vlm" and adm.next == 0
        if self._running:   # running slots wait only for THIS chunk
            self.stats["max_admit_stall_tokens"] = max(
                self.stats["max_admit_stall_tokens"], n_valid)
        t0 = time.time()
        chunk = np.zeros((1, c), np.int32)
        chunk[0, :n_valid] = req.prompt[lo:lo + n_valid]
        tokens = jax.device_put(chunk)
        img_args = ()
        if with_img:
            assert req.img_emb is not None, "vlm request needs img_emb"
            img_args = (jax.device_put(np.asarray(req.img_emb)[None]),)
        if final:
            rkey = request_key(self._base_key, adm.rid - self._key_rid0)
            eos = -1 if req.eos_id is None else int(req.eos_id)
            with self._admit_ctx():
                self._state, self._cache = self._chunk_call(True, with_img)(
                    self.params, self._state, self._cache, tokens, slot, lo,
                    n_valid, rkey, req.max_new_tokens, float(req.temperature),
                    eos, adm.poison, *img_args)
        else:
            with self._admit_ctx():
                self._cache = self._chunk_call(False, with_img)(
                    self.params, self._cache, tokens, slot, lo, n_valid,
                    *img_args)
        jax.block_until_ready(self._cache)           # honest prefill_s
        dt = time.time() - t0
        self.stats["prefill_s"] += dt
        self._req_prefill_s[adm.rid] = \
            self._req_prefill_s.get(adm.rid, 0.0) + dt
        self.stats["prefill_tokens"] += n_valid
        self._prefill_shapes.add(("chunk", c, final, with_img))
        adm.next += 1
        end = lo + n_valid
        if self._use_tree and end % self.page_size == 0 and \
                end not in self._vs_snaps.get(adm.rid, {}):
            # chunk end landed on a page boundary: snapshot the running
            # V scale so the page is insertable at retirement (a later hit
            # restores it and continues the running mean bit-exactly)
            self._vs_snaps[adm.rid][end] = (
                np.asarray(jax.device_get(self._cache["v_scale"][:, slot]))
                if self._needs_vs else None)
        if final:
            self._admitting.popleft()
            self._running[slot] = _Running(
                adm.rid, int(req.prompt.size), req.max_new_tokens,
                prompt=req.prompt if self._use_tree else None)
            self._note_first_token(slot, adm.rid)

    def _note_burst_tokens(self, t_start: float) -> None:
        """Burst-granularity inter-token bookkeeping: a burst's n tokens
        split the burst duration evenly, and the time a slot sat stalled
        BEFORE the burst (e.g. behind another request's admission) lands
        on its first token's interval — so a head-of-line-blocking prefill
        shows up as one large interval instead of being amortized away."""
        now = time.time()
        dur = now - t_start
        out_len = np.asarray(jax.device_get(self._state["out_len"]))
        for slot, info in self._running.items():
            n = int(out_len[slot] - self._prev_out_len[slot])
            if n > 0:
                per = dur / n
                stall = t_start - self._slot_last_tok.get(slot, t_start)
                self._itl.setdefault(info.rid, []).extend(
                    [stall + per] + [per] * (n - 1))
                self._slot_last_tok[slot] = now
            self._prev_out_len[slot] = out_len[slot]

    def _harvest(self) -> list[Completion]:
        """One explicit host transfer of the done/out state; frees and
        recycles every completed slot. A slot whose in-jit err flag is
        set (non-finite logits) retires with status='error' — empty
        tokens (whatever it sampled after the poison is garbage) and its
        pages are never donated to the prefix tree."""
        if not self._running:
            return []
        done = jax.device_get(self._state["done"])
        if not done.any():
            return []
        out_len = jax.device_get(self._state["out_len"])
        outs = jax.device_get(self._state["outs"])
        errf = jax.device_get(self._state["err"])
        slots = [int(s) for s in np.nonzero(done)[0] if int(s) in self._running]
        completed = []
        now = time.time()
        for slot in sorted(slots, key=lambda s: self._running[s].rid):
            info = self._running.pop(slot)
            bad = bool(errf[slot])
            toks = (np.zeros((0,), np.int32) if bad else
                    outs[slot, :int(out_len[slot])].astype(np.int32))
            if bad:
                self.stats["errors"] += 1
            else:
                self.stats["tokens_out"] += int(toks.size)
                self.stats["completed"] += 1
            if self._paged:
                self._retire_slot(slot, info, ok=not bad)
            self._free.append(slot)
            self._slot_last_tok.pop(slot, None)
            completed.append(Completion(
                info.rid, toks, now - self._submit_time.pop(info.rid),
                ttft=self._ttft.pop(info.rid, 0.0),
                ttft_wall=self._ttft_wall.pop(info.rid, 0.0),
                cached_tokens=self._cached_tokens.pop(info.rid, 0),
                itl=np.asarray(self._itl.pop(info.rid, [])),
                status="error" if bad else "completed",
                error="non-finite logits" if bad else None))
        idx = jnp.asarray(slots, jnp.int32)
        self._state = dict(self._state,
                           done=self._state["done"].at[idx].set(False),
                           err=self._state["err"].at[idx].set(False))
        return completed

    def _plan_tick(self, site: str):
        """Consult the fault plan at a hook point (no-op without one)."""
        return self._faults.tick(site) if self._faults is not None else []

    def _pop_next(self) -> tuple[int, Request]:
        """Next request to admit: highest priority first, FIFO (lowest
        rid) within a priority level. The all-default-priority case stays
        the plain O(1) popleft."""
        q = self._queue
        if len(q) > 1 and any(r.priority != q[0][1].priority for _, r in q):
            i = max(range(len(q)), key=lambda j: (q[j][1].priority, -q[j][0]))
            rid_req = q[i]
            del q[i]
            return rid_req
        return q.popleft()

    def _resolve(self, rid: int, status: str,
                 error: str | None = None) -> Completion:
        """Terminal no-token completion for a request that never reached
        a slot: shed (deadline) or error (poison / unsatisfiable pages).
        Accounts the rid exactly once, like a harvested completion."""
        self.stats["shed" if status == "shed" else "errors"] += 1
        return Completion(rid, np.zeros((0,), np.int32),
                          time.time() - self._submit_time.pop(rid),
                          status=status, error=error)

    def _run_burst(self, dr: bool, bounded: int) -> None:
        """One decode burst with fault consultation and transient-error
        retry. The 'burst' site ticks once per ATTEMPT (a retried burst
        consumes further occurrences, so `device_error@burst:i*n` models
        an n-attempt error burst); an injected fault fires BEFORE the jit
        call, so state/cache are untouched and the retried burst is
        bit-identical to an unfaulted one. Injected stalls ('slow') and
        backoff sleeps land in decode_s — they are exactly the wall time
        a goodput benchmark must see."""
        t0 = time.time()
        for attempt in range(self.burst_retries + 1):
            try:
                for f in self._plan_tick("burst"):
                    if f.kind == "slow":
                        time.sleep(f.param)       # straggler simulation
                    elif f.kind == "device_error":
                        raise TransientDeviceError(
                            f"injected device error "
                            f"(burst attempt {attempt})")
                if self._mesh is None:
                    self._state, self._cache = self._burst(
                        self.params, self._state, self._cache, dr, bounded)
                else:
                    self._state, self._cache = \
                        self._sharded_burst(dr, bounded)(
                            self.params, self._state, self._cache)
                jax.block_until_ready(self._state["done"])
                break
            except TransientDeviceError:
                self.stats["burst_retries"] += 1
                if attempt == self.burst_retries:
                    raise
                time.sleep(self.backoff_s * (2 ** attempt))
        self.stats["decode_s"] += time.time() - t0
        self.stats["bursts"] += 1
        self._note_burst_tokens(t0)

    def audit(self) -> list[str]:
        """Cross-layer invariant audit (violation strings; empty ==
        consistent): page-pool internals (`PagePool.audit`), prefix-tree
        structure (`PrefixCache.audit`), and the refcount ledger — every
        pool page's refcount must equal the references actually held by
        slot page tables plus prefix-tree nodes. Unpaged schedulers have
        nothing to audit."""
        if not self._paged:
            return []
        out = self._pager.audit()
        tree_pages: list[int] = []
        if self._use_tree:
            out += self._ptree.audit()
            tree_pages = self._ptree.pages()
        if out:
            # structurally corrupt (e.g. a tree node holding a freed or
            # out-of-range page): the ledger below would only re-report it
            return out
        expect = np.zeros((self.pool_pages,), np.int64)
        for pages in self._slot_pages.values():
            for p in pages:
                expect[p] += 1
        for p in tree_pages:
            expect[p] += 1
        return [f"page {int(p)}: pool refcount "
                f"{int(self._pager.refs[p])} != {int(expect[p])} "
                f"references held (slot tables + prefix tree)"
                for p in np.nonzero(expect != self._pager.refs)[0]]

    def _watchdog(self) -> None:
        """Invariant watchdog, run at burst boundaries when enabled
        (REPRO_CHECK_INVARIANTS=1 / check_invariants=True). On violation
        it degrades rather than crashes: the prefix tree is dropped
        (cache-bypass — slots hold their own page references, so
        in-flight requests and future uncached admissions are unaffected)
        and serving continues; only corruption that survives degradation
        (the pool ledger itself) raises InvariantViolation. The 'audit'
        fault-plan site ticks here — kind 'corrupt' deliberately corrupts
        the tree first, which is how the degradation path is tested."""
        if not (self._check_inv and self._paged):
            return
        for f in self._plan_tick("audit"):
            if f.kind == "corrupt" and self._use_tree:
                self._ptree.corrupt()
        violations = self.audit()
        if not violations:
            return
        self.stats["invariant_violations"] += 1
        self.last_violations = violations
        if self._use_tree:
            self._ptree.clear()
            self._use_tree = False
            if not self.audit():
                return                   # degraded cleanly: tree bypassed
        raise InvariantViolation("\n".join(violations))

    def poll(self, drain: bool = False) -> list[Completion]:
        """One scheduling round: admit into free slots (whole-prompt, or
        start/advance chunked admissions by AT MOST ONE chunk), harvest
        admission completions, else decode until the next completion event
        — bounded to `interleave_steps` while an admission is mid-flight
        so prompt chunks and decode bursts interleave. Leave `drain` False
        when new requests may still arrive (streaming): the burst then
        yields at every completion so a freed slot can admit them; `run()`
        passes drain=True for the tail, where nothing can arrive mid-call
        and one burst finishes every slot.

        Every submitted rid resolves to exactly one completion across the
        polls that serve it: status 'completed', 'shed' (TTFT deadline
        passed while queued — shed before any prefill compute), or
        'error' (poisoned / unsatisfiable). Completions buffered by a
        blocking submit are delivered first."""
        out, self._done_buf = self._done_buf, []
        return out + self._poll_impl(drain)

    def _poll_impl(self, drain: bool) -> list[Completion]:
        completed: list[Completion] = []
        while self._queue and self._free:
            rid, req = self._pop_next()
            if req.deadline_s is not None and \
                    time.time() - self._submit_time[rid] > req.deadline_s:
                # deadline-based load shedding: the TTFT deadline already
                # passed, so prefill compute would be wasted — shed now
                completed.append(self._resolve(rid, "shed"))
                continue
            slot = self._free.pop(0)
            poison, injected = 0.0, False
            for f in self._plan_tick("admit"):
                if f.kind == "nan":
                    poison = float("nan")
                elif f.kind == "poison":
                    injected = True
            if injected:
                self._free.insert(0, slot)
                completed.append(self._resolve(
                    rid, "error", "injected poison fault at admission"))
                continue
            if self.prefill_chunk:
                if not self._start_admission(slot, rid, req, poison):
                    self._free.insert(0, slot)
                    if not self._running and not self._admitting:
                        # nothing in flight can ever retire pages for this
                        # request: it is unsatisfiable — error it alone
                        # instead of wedging the whole scheduler
                        completed.append(self._resolve(
                            rid, "error",
                            "page pool exhausted with nothing in flight"))
                        continue
                    # page pool exhausted even after eviction: requeue and
                    # wait for in-flight requests to retire their pages
                    self._queue.appendleft((rid, req))
                    break
            else:
                self._admit(slot, rid, req, poison)
        if self._admitting:
            self._advance_admission()
        completed += self._harvest()
        if not completed and self._running:
            bounded = self.interleave_steps if self._admitting else 0
            dr = drain and not self._queue and not self._admitting
            self._run_burst(dr, bounded)
            self._watchdog()
            completed += self._harvest()
        return completed

    def run(self) -> dict[int, Completion]:
        """Poll until every submitted request has completed; return the
        completions collected along the way."""
        out: dict[int, Completion] = {}
        while not self.idle or self._done_buf:
            for c in self.poll(drain=True):
                out[c.rid] = c
        return out

    def decode_steps(self) -> int:
        # per-slot counters tick in lockstep on one device; across a mesh
        # the busiest device's count is the serving-critical-path answer
        return int(np.max(jax.device_get(self._state["steps"])))
