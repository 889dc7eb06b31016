"""Serving engine: continuous-batching runtime over the packed 1-bit model.

Inference is the paper's deployment story: weights are frozen to sign
bits (1 bit each, `packed_binary` checkpoints), all binarized matmuls are
pure XNOR+popcount, and the engine serves traffic through a slot
scheduler (`serving.scheduler`): variable-length prompts, per-request
token budgets and eos, slots recycled the moment a request completes,
sampling and token accumulation on device.

`generate(requests)` is a thin shim over the scheduler — it accepts
ragged prompt lengths and honors each request's own `max_new_tokens` /
`eos_id`. Pass `prefill_chunk=C` to admit prompts through the chunked
pipeline: fixed-shape C-token chunks interleave with bounded decode
bursts, so a long prompt's admission no longer freezes in-flight slots
(and prefill compiles once per chunk shape, never per prompt length —
with kv_bits=1 the cross-chunk attention runs XOR+popcount over the
already-written K bitplanes, `kernels.prefill_attention`). `generate_static(requests)` keeps the legacy same-length
fixed-step batch loop (the baseline the continuous-batching benchmark
compares against); it too accumulates tokens on device and transfers
once per call, never per step.

Pass `freeze=True` (or call `.freeze()`, or construct from a tree already
frozen by core.packed / restored from a packed checkpoint) to serve from
the packed runtime form: binary weights live as uint32 sign words (~32x
smaller resident footprint) and every binarized matmul runs against the
pre-packed operand — the quantize step happens once at load, never per
decode step.

Pass `kv_bits=1` (construction or `.freeze(kv_bits=1)`) to also make the
KV cache bit-resident: K/V live as uint32 sign bitplanes packed along
head_dim (+ a per-head fp V scale) and decode attention runs as
XOR+popcount over the packed words (`kernels.decode_attention`) — the
cache shrinks ~32x and with it the bytes every decode step must read,
which is what bounds decode at serving scale. `resident_cache_bytes()`
reports the split the same way `resident_weight_bytes()` does for
weights.

Pass `page_size=P` (attention families, with `prefill_chunk`) to replace
the contiguous per-slot cache with the paged layout: K/V pages in a
shared refcounted pool addressed through per-slot page tables
(`serving.pager`), and `prefix_cache=True` to share identical prompt
prefixes across requests zero-copy through a radix tree over full pages
(`serving.prefix_cache`) — admission pins matched pages into the new
slot's table and prefills only the unseen suffix.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.packed import params_frozen, resident_weight_bytes
from repro.models.api import get_model
from repro.serving.scheduler import Request, Scheduler

__all__ = ["Request", "Scheduler", "ServingEngine"]


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 512,
                 mesh=None, freeze: bool = False, slots: int = 4,
                 seed: int = 0, kv_bits: int | None = None,
                 prefill_chunk: int | None = None,
                 interleave_steps: int = 8, page_size: int | None = None,
                 pool_pages: int | None = None, prefix_cache: bool = False,
                 queue_cap: int | None = None, overflow: str = "reject",
                 fault_plan=None, check_invariants: bool | None = None):
        if kv_bits is not None:
            if kv_bits not in (0, 1):
                raise ValueError(f"kv_bits must be 0 (float cache) or 1 "
                                 f"(packed sign bitplanes), got {kv_bits}")
            cfg = cfg.scaled(kv_bits=kv_bits)
        self.cfg = cfg
        self.model = get_model(cfg)
        self.params = params
        self.max_len = max_len
        self.mesh = mesh
        self.slots = slots
        self.prefill_chunk = prefill_chunk
        self.interleave_steps = interleave_steps
        self.page_size = page_size
        self.pool_pages = pool_pages
        self.prefix_cache = prefix_cache
        self.queue_cap = queue_cap
        self.overflow = overflow
        self.fault_plan = fault_plan
        self.check_invariants = check_invariants
        self.frozen = params_frozen(params)
        self._key = jax.random.PRNGKey(seed)
        self._sched: Scheduler | None = None
        if freeze:
            self.freeze()
        self._build_step_fns()

    def _build_step_fns(self) -> None:
        self._decode = jax.jit(self.model.decode, donate_argnums=(2,))
        self._prefill = jax.jit(
            lambda p, t: self.model.prefill(
                p, t, **({"max_len": self.max_len}
                         if self.cfg.family in ("dense", "moe", "audio", "vlm")
                         else {})))

    def freeze(self, kv_bits: int | None = None) -> "ServingEngine":
        """Freeze fp32 masters to packed 1-bit weights, in place.

        Load-time quantization: after this, batched decode runs entirely
        on packed weights (XNOR+popcount) and the fp32 masters are gone.
        Pass `kv_bits=1` to additionally switch the KV cache to packed
        sign bitplanes (the bit-resident decode-attention kernel) — the
        cache is rebuilt, so like weight freezing it requires an idle
        scheduler. Idempotent; returns self for chaining.
        """
        if not self.frozen:
            if self._sched is not None and not self._sched.idle:
                raise RuntimeError(
                    "cannot freeze with requests in flight — drain the "
                    "scheduler (run()) first")
            self.params = self.model.freeze(self.params)
            self.frozen = True
            self._sched = None     # rebuild over the frozen params
        if kv_bits is not None and kv_bits != self.cfg.kv_bits:
            if kv_bits not in (0, 1):
                raise ValueError(f"kv_bits must be 0 or 1, got {kv_bits}")
            if self._sched is not None and not self._sched.idle:
                raise RuntimeError(
                    "cannot change kv_bits with requests in flight — drain "
                    "the scheduler (run()) first")
            self.cfg = self.cfg.scaled(kv_bits=kv_bits)
            self.model = get_model(self.cfg)
            self._sched = None     # cache layout changed: rebuild
            self._build_step_fns()
        return self

    def resident_weight_bytes(self) -> dict:
        """Bytes of weights resident in memory, split binary vs other."""
        return resident_weight_bytes(self.params)

    def _cache_kw(self) -> dict:
        """init_cache kwargs for this engine's cache layout — paged for
        the attention families when page_size is set (same default pool
        sizing as the scheduler), empty (contiguous) otherwise."""
        if self.page_size is None or \
                self.cfg.family not in ("dense", "moe", "audio", "vlm"):
            return {}
        n_pages = -(-self.max_len // self.page_size)
        return {"page_size": self.page_size,
                "pool_pages": (self.pool_pages if self.pool_pages is not None
                               else self.slots * n_pages)}

    def resident_cache_bytes(self) -> dict:
        """Bytes of KV cache / recurrent state resident for this engine's
        slot allocation (`slots` rows at `max_len`), split `packed` (uint32
        sign bitplanes, kv_bits=1) vs `float` (fp K/V, V scales, recurrent
        states). Family-aware by construction — it walks whatever leaves
        this family's `init_cache` actually allocates, so with `page_size`
        set it reports the page-pool layout (pool K/V + page tables).
        Computed from abstract shapes; nothing is materialized. With a
        live paged scheduler, also merges the pool utilization split —
        pages allocated to slots vs pinned only by the prefix tree vs
        free (`page_stats`)."""
        cache_kw = self._cache_kw()
        cache = jax.eval_shape(
            lambda: self.model.init_cache(self.slots, self.max_len,
                                          **cache_kw))
        out = {"packed": 0, "float": 0}
        for leaf in jax.tree.leaves(cache):
            nbytes = int(np.prod(leaf.shape, dtype=np.int64)) * \
                jnp.dtype(leaf.dtype).itemsize
            kind = "packed" if leaf.dtype == jnp.uint32 else "float"
            out[kind] += nbytes
        out["total"] = out["packed"] + out["float"]
        if self._sched is not None:
            ps = self._sched.page_stats()
            if ps is not None:
                out["page_pool"] = ps
        return out

    def resident_bytes_per_device(self) -> dict:
        """Live per-device residency under a mesh: for every device, the
        bytes of weights / KV cache (or recurrent state) / serving state
        it actually holds, summed over the *local shards* of the
        scheduler's placed arrays — replicated leaves (packed weights,
        paged pools) count their full size on every device, batch-sharded
        leaves only their slot shard. Requires `mesh`; builds the
        scheduler if needed (that is where placement happens)."""
        assert self.mesh is not None, "resident_bytes_per_device needs a mesh"
        sched = self.scheduler()
        out: dict = {}

        def add(tree, kind: str) -> None:
            for leaf in jax.tree.leaves(tree):
                if not isinstance(leaf, jax.Array):
                    continue
                for sh in leaf.addressable_shards:
                    d = out.setdefault(
                        str(sh.device), {"weights": 0, "cache": 0, "state": 0})
                    d[kind] += int(sh.data.nbytes)

        add(self.params, "weights")
        add(sched._cache, "cache")
        add(sched._state, "state")
        for d in out.values():
            d["total"] = d["weights"] + d["cache"] + d["state"]
        return out

    def kernel_routes(self) -> dict:
        """Resolved kernel routes (repro.kernels.tune) for this engine's
        characteristic shapes: which realization each packed kernel will
        actually run at serving time — 'vpu'/'mxu'/'xla'/'float' for the
        binary GEMMs, 'pallas'/'xla' for the packed attention. Pure
        lookup (cache hit or heuristic); diagnostic only — dispatch
        happens inside the jitted step functions from the same cache, so
        this is exactly what they resolved at trace time."""
        from repro.core.bitpack import packed_width
        from repro.kernels import tune
        cfg, m, out = self.cfg, self.slots, {}
        for k, n in [(cfg.d_model, cfg.d_model), (cfg.d_model, cfg.d_ff),
                     (cfg.d_ff, cfg.d_model)]:
            if k and n:
                # both lhs forms run at serve time: float at the chain entry
                # (pl=0), packed wire-format words after (pl=1) — the cache
                # keys them separately because they run different kernels
                for pl, tag in ((1, "bits"), (0, "f32")):
                    out[f"binary_gemm_fused[{m}x{k}->{n}|{tag}]"] = \
                        tune.get_route("binary_gemm_fused", m=m, n=n,
                                       kw=packed_width(k), pl=pl)
        if cfg.n_kv_heads:
            g = max(1, cfg.n_heads // cfg.n_kv_heads)
            paged = bool(self._cache_kw())
            if paged:
                ps = self.page_size
                np_ = -(-self.max_len // ps)
                pool = self._cache_kw()["pool_pages"]
                out[f"decode_attention_paged[b{m}_t{np_ * ps}_ps{ps}]"] = \
                    tune.get_route("decode_attention_paged", b=m,
                                   t=np_ * ps, ps=ps, p=pool,
                                   hkv=cfg.n_kv_heads, g=g, hd=cfg.head_dim)
                if self.prefill_chunk:
                    out[f"prefill_attention_paged[b{m}"
                        f"_s{self.prefill_chunk}_t{np_ * ps}_ps{ps}]"] = \
                        tune.get_route("prefill_attention_paged", b=m,
                                       s=self.prefill_chunk, t=np_ * ps,
                                       ps=ps, p=pool, hkv=cfg.n_kv_heads,
                                       g=g, hd=cfg.head_dim)
            else:
                out[f"decode_attention[b{m}_t{self.max_len}]"] = \
                    tune.get_route("decode_attention", b=m, t=self.max_len,
                                   hkv=cfg.n_kv_heads, g=g, hd=cfg.head_dim)
                if self.prefill_chunk:
                    out[f"prefill_attention[b{m}_s{self.prefill_chunk}"
                        f"_t{self.max_len}]"] = tune.get_route(
                        "prefill_attention", b=m, s=self.prefill_chunk,
                        t=self.max_len, hkv=cfg.n_kv_heads, g=g,
                        hd=cfg.head_dim)
        return out

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def scheduler(self) -> Scheduler:
        """The engine's continuous-batching scheduler (built lazily).
        `prefill_chunk` (construction arg) switches admission to the
        chunked pipeline: prompts advance through the slot cache in
        fixed-shape chunks interleaved with bounded decode bursts."""
        if self._sched is None:
            self._sched = Scheduler(self.cfg, self.model, self.params,
                                    n_slots=self.slots, max_len=self.max_len,
                                    prefill_chunk=self.prefill_chunk,
                                    interleave_steps=self.interleave_steps,
                                    page_size=self.page_size,
                                    pool_pages=self.pool_pages,
                                    prefix_cache=self.prefix_cache,
                                    mesh=self.mesh,
                                    queue_cap=self.queue_cap,
                                    overflow=self.overflow,
                                    fault_plan=self.fault_plan,
                                    check_invariants=self.check_invariants)
            if self.mesh is not None:
                # the scheduler replicated the params over the mesh —
                # serve the engine's other paths from the same placement
                self.params = self._sched.params
        return self._sched

    def serve(self, requests: list[Request], key=None) -> list:
        """Like `generate`, but returns the full `Completion` objects —
        including `status` ('completed' / 'shed' / 'error') and `error` —
        in request order. `generate` is the tokens-only shim over this;
        resilience-aware callers (ReplicaServer failover, benchmarks)
        need the statuses to account for every request exactly once."""
        assert requests, "empty batch"
        sched = self.scheduler()
        sched.reseed(key if key is not None else self._next_key())
        rids = [sched.submit(r) for r in requests]
        comps = sched.run()
        return [comps[rid] for rid in rids]

    def generate(self, requests: list[Request], key=None) -> list[np.ndarray]:
        """Generate for a batch of requests — ragged prompt lengths,
        per-request budgets/eos — through the slot scheduler. Shed or
        errored requests come back as empty token arrays (use `serve`
        for the statuses).

        With temperature > 0 and no explicit `key`, samples draw from the
        engine's held key, split per call: repeated calls give fresh
        samples; pass `key` to reproduce a draw.
        """
        return [c.tokens for c in self.serve(requests, key=key)]

    def generate_static(self, requests: list[Request], key=None
                        ) -> list[np.ndarray]:
        """Legacy static batch loop: same-length prompts, every request
        decoded for the batch-max number of steps. Tokens accumulate on
        device and transfer once at the end — no per-step host sync."""
        assert requests, "empty batch"
        lens = {len(r.prompt) for r in requests}
        assert len(lens) == 1, "static path batches same-length prompts"
        s = lens.pop()
        max_new = max(r.max_new_tokens for r in requests)
        tokens = jax.device_put(
            np.stack([np.asarray(r.prompt, np.int32) for r in requests]))
        if key is None and any(r.temperature > 0 for r in requests):
            key = self._next_key()

        logits, cache = self._prefill(self.params, tokens)
        cur = self._select(logits, requests, key, 0)
        steps = [cur]
        for i in range(max_new - 1):
            logits, cache = self._decode(self.params, cur, cache,
                                         jnp.int32(s + i))
            cur = self._select(logits, requests, key, i + 1)
            steps.append(cur)
        out = jax.device_get(jnp.stack(steps, axis=1))   # ONE transfer
        # the batch decodes max(max_new_tokens) steps together; honor each
        # request's own budget in what we hand back
        return [out[j, :r.max_new_tokens].astype(np.int32)
                for j, r in enumerate(requests)]

    def _select(self, logits, requests, key, i):
        if all(r.temperature == 0.0 for r in requests):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        assert key is not None, "sampling needs a key (engine supplies one)"
        k = jax.random.fold_in(key, i)
        temp = jnp.asarray([max(r.temperature, 1e-4) for r in requests])
        return jax.random.categorical(k, logits / temp[:, None], axis=-1
                                      ).astype(jnp.int32)
