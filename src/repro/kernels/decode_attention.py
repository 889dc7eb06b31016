"""Decode attention over a bit-resident KV cache: Pallas kernel + dispatch.

The serving-path complement of `binary_gemm_vpu_packed_io`: after PRs 1-3
froze weights and inter-layer activations to sign bits, the float KV cache
was the last non-bit-resident tensor in the frozen decode path — and decode
is bound by reading it, the exact 32x activation-memory tax the paper's
XNOR+popcount formulation exists to remove. With `kv_bits=1` the cache
stores K and V as wire-format uint32 bitplanes (sign bits packed along
head_dim, `ceil(hd/32)` words per position, pad bits 1) plus one fp scale
per (batch row, kv head) for V, and this kernel computes the whole decode
step on the packed words:

  * scores: the sign-packed query is XOR'd against each packed K row and
    popcounted on the VPU lanes — `q.k = hd - 2*popcount(xor)` — never
    unpacking K;
  * masking: per-slot `(B,)` cache lengths and an optional sliding window
    are applied in VMEM (a continuous-batching slot batch holds every row
    at its own offset);
  * softmax: max/exp/sum in VMEM, fp32;
  * V accumulation: packed V unpacks to +-1 *in VMEM only* and accumulates
    under the softmax weights with the same K-2*acc sign trick, scaled by
    the per-head fp `v_scale`.

Float K/V are never materialized in HBM: HBM traffic per decode step drops
from `2*B*T*Hkv*hd*itemsize` to `2*B*T*Hkv*ceil(hd/32)*4` bytes (~32x for
fp32 caches at hd >= 32).

The kernel is the chunked-prefill kernel at S == 1
(`kernels.prefill_attention.packed_attention` with q_pos = cache_len - 1,
whose causal mask is then exactly `t < cache_len`): grid (B/block_b, Hkv),
each program owning `block_b` batch rows of one kv head and their full
(hdw, T) K/V panels in VMEM. `block_b` is an autotuned knob
(repro.kernels.tune) — one row per program maximizes grid parallelism,
several rows per program amortize per-program overhead. GQA query heads
for the kv head ride in the same block.

`decode_attention_packed` is the dispatching entry point: `route=None`
consults the tuning cache, which may pick this Pallas kernel ('pallas',
with a tuned `block_b`) or the XLA-lowered packed formulation ('xla', the
oracle itself — on hosts where Pallas runs in interpret mode, letting XLA
compile the popcount einsum is the fast packed path). Semantics are
defined by `repro.kernels.ref.decode_attention_packed_ref`; the routes
agree on the integer scores exactly and on the output up to f32 rounding
of the softmax and V sums (tests/test_decode_attention_packed.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.prefill_attention import packed_attention

Array = jax.Array


def v_cache_scale(v: Array) -> Array:
    """Per-(row, kv-head) V magnitude for a packed cache: mean |v| over
    (positions, head_dim) of a (B, S, Hkv, hd) float V. The one fp number
    per head that rides with the V bitplane (XNOR-net style scaling) —
    `out = v_scale * sum_t p_t * sign(v_t)` — fixed at prefill. Single
    definition for every family that packs a cache (transformer KV, hybrid
    ring buffer), so their wire formats cannot drift."""
    return jnp.mean(jnp.abs(v.astype(jnp.float32)), axis=(1, 3))


def decode_attention_packed(q: Array, k_packed: Array, v_packed: Array,
                            v_scale: Array, cache_len: Array, *,
                            window: int = 0, block_b: int | None = None,
                            route: str | None = None,
                            interpret: bool | None = None) -> Array:
    """Single-token decode attention against a bit-resident KV cache.

    q: (B, 1, Hq, hd) float (sign-packed here — one pack per step);
    k_packed, v_packed: (B, T_max, Hkv, ceil(hd/32)) uint32 wire-format sign
    bitplanes (pad bits 1, so an odd head_dim's tail cancels in the xor);
    v_scale: (B, Hkv) float per-head V magnitude (fixed at prefill);
    cache_len: scalar or (B,) valid positions — the new token is already
    written at cache_len-1. Masks positions >= cache_len and, when
    window > 0, positions < cache_len - window. Returns (B, 1, Hq, hd) in
    q.dtype, equal to ref.decode_attention_packed_ref up to f32 rounding
    of the softmax and V sums.

    route=None consults the tuning cache ('pallas' with a tuned block_b,
    or 'xla'); an explicit route (+ block_b) bypasses it — tests and the
    autotuner pin candidates that way.
    """
    b, t, hkv, hdw = k_packed.shape
    hd = q.shape[-1]
    g = q.shape[2] // hkv
    if route is None:
        from repro.kernels import tune
        route, params = tune.get_route("decode_attention", b=b, t=t,
                                       hkv=hkv, g=g, hd=hd)
        if block_b is None:
            block_b = params.get("block_b")
    if route == "xla":
        return ref.decode_attention_packed_ref(q, k_packed, v_packed,
                                               v_scale, cache_len,
                                               window=window)
    if route != "pallas":
        raise ValueError(f"unknown decode_attention route: {route}")
    lens = jnp.asarray(cache_len, jnp.int32)
    return packed_attention(q, k_packed, v_packed, v_scale, lens, lens - 1,
                            window=window, causal=True, block_q=1,
                            block_b=block_b or 1, interpret=interpret)


def decode_attention_packed_paged(q: Array, k_pool: Array, v_pool: Array,
                                  v_scale: Array, page_table: Array,
                                  cache_len: Array, *, window: int = 0,
                                  block_b: int | None = None,
                                  route: str | None = None,
                                  interpret: bool | None = None) -> Array:
    """Single-token decode attention against a *paged* bit-resident cache.

    q: (B, 1, Hq, hd) float; k_pool, v_pool: (P, ps, Hkv, ceil(hd/32))
    uint32 page pools shared by every slot; page_table: (B, NP) int32
    mapping each slot's position range [i*ps, (i+1)*ps) to a pool page
    (entries == P are the unallocated sentinel — they clip to the last
    page and the garbage is masked by cache_len); v_scale: (B, Hkv);
    cache_len: scalar or (B,). Returns (B, 1, Hq, hd) in q.dtype, equal
    to ref.decode_attention_packed_paged_ref up to f32 rounding — and
    bit-exact with the contiguous `decode_attention_packed` whenever NP*ps
    equals its T (one kernel core; paging is pure addressing).
    """
    p_pool, ps, hkv, hdw = k_pool.shape
    b, np_ = page_table.shape
    hd = q.shape[-1]
    g = q.shape[2] // hkv
    if route is None:
        from repro.kernels import tune
        route, params = tune.get_route("decode_attention_paged", b=b,
                                       t=np_ * ps, ps=ps, p=p_pool,
                                       hkv=hkv, g=g, hd=hd)
        if block_b is None:
            block_b = params.get("block_b")
    if route == "xla":
        return ref.decode_attention_packed_paged_ref(
            q, k_pool, v_pool, v_scale, page_table, cache_len, window=window)
    if route != "pallas":
        raise ValueError(f"unknown decode_attention_paged route: {route}")
    lens = jnp.asarray(cache_len, jnp.int32)
    return packed_attention(q, k_pool, v_pool, v_scale, lens, lens - 1,
                            window=window, causal=True, block_q=1,
                            block_b=block_b or 1, interpret=interpret,
                            page_table=page_table)
