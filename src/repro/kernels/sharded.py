"""Tensor-parallel shard_map wrappers over the packed kernel dispatchers.

`pallas_call` is opaque to XLA's auto-sharding: under a plain GSPMD jit a
sharded operand reaching a Pallas kernel is all-gathered (or the lowering
fails outright), so the popcount kernels cannot be *partitioned* — but
they can be *mapped*: under `jax.shard_map` every device
traces the same kernel over its local shard, grids and block geometry
derive from the local shape, and the tuning cache is consulted at the
local shape too (a device owning Hkv/4 heads tunes like a 4x-smaller
kernel, which is exactly what it is).

Layout contract (matches `launch.shardings.cache_shardings`):

  * GEMMs are column-parallel: the weight bitplane `(N, KW)` shards its
    output-feature axis N over the mesh axis; the uint32 word axis KW is
    NEVER split — a word is the kernel's indivisible popcount unit. The
    fused GEMM additionally requires each N shard to stay a multiple of
    32 so the per-device output *words* concatenate into the unsharded
    wire format (`_geometry.shard_geometry(multiple=WORD)`).
    Row-parallel (K-sharded) splits are deliberately not offered: the
    fused kernel's sign-threshold epilogue needs the *complete* integer
    dot before comparing against `thresh`, so a K split would force an
    int32 psum before the epilogue — all the traffic the fused wire
    format exists to avoid.
  * Attention shards the Hkv grid axis: each device owns Hkv/parts kv
    heads, their GQA query group (q heads are kv-major, so the split is
    a contiguous reshape), and their slice of `v_scale`. K/V bitplanes
    shard the Hkv axis and replicate the word axis; the paged pools
    shard Hkv the same way while the page *table* stays replicated —
    every device gathers the same pages, just for its own heads.

Every wrapper returns the same global value as its unsharded dispatcher
(bit-exact: the local kernels are bit-exact vs ref at every shape, and
the head/N axis is data-independent), with outputs left sharded on the
same axis so chained layers keep the layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.bitpack import WORD
from repro.kernels import decode_attention as DA
from repro.kernels import prefill_attention as PA
from repro.kernels._geometry import shard_geometry
from repro.kernels.binary_gemm import (
    dispatch_binary_gemm, dispatch_binary_gemm_fused,
)

Array = jax.Array


def _parts(mesh, axis: str) -> int:
    assert axis in mesh.axis_names, (axis, mesh.axis_names)
    return mesh.shape[axis]


def binary_gemm_tp(a: Array, b_packed: Array, k_true: int, *, mesh,
                   axis: str = "model", route: str | None = None,
                   interpret: bool | None = None) -> Array:
    """Column-parallel `dispatch_binary_gemm`: b_packed (N, KW) sharded on
    N over `axis`, activations replicated, (M, N) int32 out sharded on N."""
    n = b_packed.shape[0]
    shard_geometry(n, _parts(mesh, axis), name="n")

    def body(a, bp):
        return dispatch_binary_gemm(a, bp, k_true, route=route,
                                    interpret=interpret)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P(axis, None)),
                         out_specs=P(None, axis), check_vma=False)(a, b_packed)


def binary_gemm_fused_tp(a: Array, b_packed: Array, thresh: Array,
                         flip: Array, k_true: int, *, mesh,
                         axis: str = "model", route: str | None = None,
                         interpret: bool | None = None) -> Array:
    """Column-parallel fused GEMM: b_packed/thresh/flip shard N over
    `axis`; each device runs the full popcount + sign-threshold + repack
    pipeline on its N slice and the (M, ceil(N/32)) uint32 output words
    concatenate along the word axis (N shards are kept 32-aligned, so
    local word k is global word `device_offset/32 + k`)."""
    n = b_packed.shape[0]
    shard_geometry(n, _parts(mesh, axis), name="n", multiple=WORD)

    def body(a, bp, th, fl):
        return dispatch_binary_gemm_fused(a, bp, th, fl, k_true, route=route,
                                          interpret=interpret)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(), P(axis, None), P(axis), P(axis)),
                         out_specs=P(None, axis),
                         check_vma=False)(a, b_packed, thresh, flip)


def _split_heads(q: Array, hkv: int):
    """(B, S, Hq, hd) -> (B, S, Hkv, G, hd): q heads are kv-major, so a
    per-kv-head shard is a contiguous slice of this reshape."""
    b, s, hq, hd = q.shape
    return q.reshape(b, s, hkv, hq // hkv, hd)


def _rows(x, b: int) -> Array:
    """Scalar-or-(B,) per-row value -> concrete (B,) i32 (replicated)."""
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (b,))


def decode_attention_packed_tp(q: Array, k_packed: Array, v_packed: Array,
                               v_scale: Array, cache_len, *, mesh,
                               axis: str = "model", window: int = 0,
                               route: str | None = None,
                               interpret: bool | None = None) -> Array:
    """Hkv-sharded `decode_attention_packed`: each device attends its own
    kv heads (full T, word axis replicated) for the whole batch."""
    b, _, hkv, _ = k_packed.shape
    shard_geometry(hkv, _parts(mesh, axis), name="hkv")
    q5, lens = _split_heads(q, hkv), _rows(cache_len, b)

    def body(q5, kb, vb, vs, lens):
        bl, s, hl, g, hd = q5.shape
        out = DA.decode_attention_packed(
            q5.reshape(bl, s, hl * g, hd), kb, vb, vs, lens,
            window=window, route=route, interpret=interpret)
        return out.reshape(bl, s, hl, g, hd)

    hs = P(None, None, axis, None, None)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(hs, P(None, None, axis, None),
                                  P(None, None, axis, None), P(None, axis), P()),
                        out_specs=hs, check_vma=False)(
        q5, k_packed, v_packed, v_scale, lens)
    return out.reshape(q.shape)


def decode_attention_packed_paged_tp(q: Array, k_pool: Array, v_pool: Array,
                                     v_scale: Array, page_table: Array,
                                     cache_len, *, mesh, axis: str = "model",
                                     window: int = 0,
                                     route: str | None = None,
                                     interpret: bool | None = None) -> Array:
    """Paged twin: pools (P, ps, Hkv, w) shard Hkv, the page table stays
    replicated — every device walks the same table for its own heads."""
    hkv = k_pool.shape[2]
    b = page_table.shape[0]
    shard_geometry(hkv, _parts(mesh, axis), name="hkv")
    q5, lens = _split_heads(q, hkv), _rows(cache_len, b)

    def body(q5, kp, vp, vs, pt, lens):
        bl, s, hl, g, hd = q5.shape
        out = DA.decode_attention_packed_paged(
            q5.reshape(bl, s, hl * g, hd), kp, vp, vs, pt, lens,
            window=window, route=route, interpret=interpret)
        return out.reshape(bl, s, hl, g, hd)

    hs = P(None, None, axis, None, None)
    pool = P(None, None, axis, None)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(hs, pool, pool, P(None, axis), P(), P()),
                        out_specs=hs, check_vma=False)(
        q5, k_pool, v_pool, v_scale, page_table, lens)
    return out.reshape(q.shape)


def prefill_attention_packed_tp(q: Array, k_packed: Array, v_packed: Array,
                                v_scale: Array, kv_len, q_pos, *, mesh,
                                axis: str = "model", window: int = 0,
                                causal: bool = True,
                                route: str | None = None,
                                interpret: bool | None = None) -> Array:
    """Hkv-sharded `prefill_attention_packed` (chunked-prefill S > 1)."""
    b, _, hkv, _ = k_packed.shape
    shard_geometry(hkv, _parts(mesh, axis), name="hkv")
    q5 = _split_heads(q, hkv)
    lens, pos = _rows(kv_len, b), _rows(q_pos, b)

    def body(q5, kb, vb, vs, lens, pos):
        bl, s, hl, g, hd = q5.shape
        out = PA.prefill_attention_packed(
            q5.reshape(bl, s, hl * g, hd), kb, vb, vs, lens, pos,
            window=window, causal=causal, route=route, interpret=interpret)
        return out.reshape(bl, s, hl, g, hd)

    hs = P(None, None, axis, None, None)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(hs, P(None, None, axis, None),
                                  P(None, None, axis, None), P(None, axis),
                                  P(), P()),
                        out_specs=hs, check_vma=False)(
        q5, k_packed, v_packed, v_scale, lens, pos)
    return out.reshape(q.shape)


def prefill_attention_packed_paged_tp(q: Array, k_pool: Array, v_pool: Array,
                                      v_scale: Array, page_table: Array,
                                      kv_len, q_pos, *, mesh,
                                      axis: str = "model", window: int = 0,
                                      causal: bool = True,
                                      route: str | None = None,
                                      interpret: bool | None = None) -> Array:
    """Paged twin of the prefill wrapper: pools shard Hkv, table replicated."""
    hkv = k_pool.shape[2]
    b = page_table.shape[0]
    shard_geometry(hkv, _parts(mesh, axis), name="hkv")
    q5 = _split_heads(q, hkv)
    lens, pos = _rows(kv_len, b), _rows(q_pos, b)

    def body(q5, kp, vp, vs, pt, lens, pos):
        bl, s, hl, g, hd = q5.shape
        out = PA.prefill_attention_packed_paged(
            q5.reshape(bl, s, hl * g, hd), kp, vp, vs, pt, lens, pos,
            window=window, causal=causal, route=route, interpret=interpret)
        return out.reshape(bl, s, hl, g, hd)

    hs = P(None, None, axis, None, None)
    pool = P(None, None, axis, None)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(hs, pool, pool, P(None, axis), P(), P(), P()),
                        out_specs=hs, check_vma=False)(
        q5, k_pool, v_pool, v_scale, page_table, lens, pos)
    return out.reshape(q.shape)
