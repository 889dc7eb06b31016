"""Pure-jnp oracles for the binary GEMM kernels.

These define the semantics the Pallas kernels must match bit-exactly:
    binary_matmul(x, w) == sign(x) @ sign(w)
with sign(0) := +1 (the paper's Eq. 5 convention, matching binarize_det).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.bitpack import pack_bits, packed_dot, unpack_bits

Array = jax.Array


def sign_pm1(x: Array) -> Array:
    return jnp.where(x >= 0, 1.0, -1.0).astype(jnp.float32)


def binary_matmul_ref(x: Array, w: Array) -> Array:
    """Dense float oracle: sign(x) @ sign(w). x: (M, K), w: (K, N)."""
    return jnp.matmul(sign_pm1(x), sign_pm1(w)).astype(jnp.float32)


def binary_matmul_packed_ref(a_packed: Array, b_packed: Array, k: int) -> Array:
    """Packed oracle. a_packed: (M, KW) uint32, b_packed: (N, KW) uint32
    (rhs packed along K after transpose). Returns (M, N) int32."""
    return packed_dot(a_packed[:, None, :], b_packed[None, :, :], k)


def binary_matmul_fused_ref(a_packed: Array, b_packed: Array, thresh: Array,
                            flip: Array, k: int) -> Array:
    """Oracle for the fused packed-I/O epilogue (binary_gemm_vpu_packed_io):
    popcount dot -> per-channel threshold bit -> wire-format repack along N.
    a_packed: (M, KW) uint32, b_packed: (N, KW) uint32, thresh/flip: (N,)
    int32. Returns (M, ceil(N/32)) uint32, pad bits 1."""
    ints = packed_dot(a_packed[:, None, :], b_packed[None, :, :], k)  # (M, N)
    bits = (ints >= thresh[None, :]) != (flip[None, :] != 0)
    return pack_bits(jnp.where(bits, 1.0, -1.0))


# Fixed-point softmax. The sign-dot scores are integers, so the softmax
# numerators take at most hd + 1 values: exp(-2j/sqrt(hd)) for j = popcount
# minus the row's smallest popcount. Each is built by a fixed chain of f32
# multiplies (the same IEEE products on every backend) and held as a 30-bit
# fixed-point integer in two 15-bit limbs; the row sums and the V sums are
# then exact integer sums, the same in any order. That is what lets the
# Pallas kernels (which reduce in tiles, and sum V on the MXU) agree with
# these oracles bit for bit on every backend.
LIMB = 15


def _softmax_rungs(hd: int) -> list[float]:
    """exp(-2^k * 2/sqrt(hd)) for every bit k of a popcount gap j <= hd."""
    return [math.exp(-(2 ** k) * 2.0 / math.sqrt(hd))
            for k in range(hd.bit_length())]


def softmax_weights(pc: Array, valid: Array, hd: int,
                    lowest: Array | None = None) -> Array:
    """Fixed-point softmax numerators from xor popcounts.

    pc: (..., T) int32 popcount(xor(q_bits, k_bits_t)) — the sign dot is
    hd - 2*pc, so the lowest popcount is the top score; valid: bool,
    broadcastable to pc; lowest: (..., 1) the row's lowest valid popcount
    (hd + 1 if none), when pc holds only part of the row. Returns
    (..., T) int32 in [0, 2^30]: 2^30 * exp(score_t - max score), masked
    positions 0."""
    pc = jnp.where(valid, pc, jnp.int32(hd + 1))
    if lowest is None:
        lowest = jnp.min(pc, axis=-1, keepdims=True)
    j = pc - lowest
    e = jnp.ones(j.shape, jnp.float32)
    for k, rung in enumerate(_softmax_rungs(hd)):
        e = jnp.where(((j >> k) & 1) != 0, e * jnp.float32(rung), e)
    w = (e * jnp.float32(1 << (2 * LIMB))).astype(jnp.int32)
    return jnp.where(valid, w, 0)


def limb_sums(w: Array) -> tuple[Array, Array]:
    """Exact sum of fixed-point weights over the last axis, as (high limb
    sum, low limb sum) — each fits int32 for T < 2^16."""
    return (jnp.sum(w >> LIMB, axis=-1, keepdims=True),
            jnp.sum(w & ((1 << LIMB) - 1), axis=-1, keepdims=True))


def exact_bits_dot(w: Array, bits: Array, dims) -> tuple[Array, Array]:
    """sum_t w_t * bits_t, exact on any matmul unit, as (high limb, low
    limb) int32 sums. Each 15-bit limb splits into bytes that bf16 holds
    exactly, and a byte plane's sum stays below 2^24 for T < 65536, so
    the f32 accumulation is exact in any order. `dims` are
    lax.dot_general dimension numbers."""
    b = bits.astype(jnp.float32).astype(jnp.bfloat16)

    def plane(x):
        return jax.lax.dot_general(
            x.astype(jnp.float32).astype(jnp.bfloat16), b, dims,
            preferred_element_type=jnp.float32).astype(jnp.int32)

    def limb(x):
        return (plane(x >> 8) << 8) + plane(x & 255)

    return limb(w >> LIMB), limb(w & ((1 << LIMB) - 1))


def fixed_point_out(sp, l, v_scale: Array) -> Array:
    """v_scale * sum_t p_t sign(v_t) from the exact limb sums: sp = sums of
    w_t * bit(v_t) (..., hd), l = sums of w_t (..., 1), both (high, low).
    sign = 2*bit - 1, so the signed sum is sp - (l - sp) per limb. A row
    with no valid position (l == 0) gives 0."""
    def value(hi, lo):
        return hi.astype(jnp.float32) * jnp.float32(1 << LIMB) + \
            lo.astype(jnp.float32)

    acc = value(*(s - (t - s) for s, t in zip(sp, l)))
    den = jnp.maximum(value(*l), jnp.float32(1.0))
    return v_scale * (acc / den)


def decode_attention_packed_ref(q: Array, k_packed: Array, v_packed: Array,
                                v_scale: Array, cache_len: Array, *,
                                window: int = 0) -> Array:
    """Oracle for kernels.decode_attention.decode_attention_packed.

    Defines the quantized decode-attention semantics the Pallas kernel must
    match bit-exactly: the KV cache holds only sign bits (packed along
    head_dim, pad bits 1) plus a per-head fp scale for V, so

        score_t = (hd - 2*popcount(xor(q_bits, k_bits_t))) / sqrt(hd)
        out     = v_scale * softmax(score)_t . sign(v_t)

    with the softmax in 30-bit fixed point (`softmax_weights`).
    q: (B, 1, Hq, hd) float; k_packed/v_packed: (B, T, Hkv, hdw) uint32;
    v_scale: (B, Hkv) float; cache_len: scalar or (B,) valid positions.
    Masks positions >= cache_len and (window > 0) outside the window —
    the chunk oracle at S == 1, q_pos == cache_len - 1.
    """
    lens = jnp.asarray(cache_len, jnp.int32)
    return prefill_attention_packed_ref(q, k_packed, v_packed, v_scale,
                                        lens, lens - 1, window=window)


def packed_masked_attention_ref(q: Array, k_packed: Array, v_packed: Array,
                                v_scale: Array, valid: Array) -> Array:
    """Quantized multi-query attention core with an explicit (B, S, T)
    validity mask — the single definition of the packed-attention op
    sequence (pack -> xor popcount -> fixed-point softmax weights -> exact
    weighted V-bit sums -> v_scale * acc / l) that the prefill oracle AND
    the rg ring-buffer chunk attention both call.

    q: (B, S, Hq, hd) float; k_packed/v_packed: (B, T, Hkv, hdw) uint32;
    v_scale: (B, Hkv) float. Returns (B, S, Hq, hd) in q.dtype."""
    b, t, hkv, hdw = k_packed.shape
    s = q.shape[1]
    hd = q.shape[-1]
    g = q.shape[2] // hkv
    qb = pack_bits(q.reshape(b, s, hkv, g, hd).transpose(0, 2, 1, 3, 4))
    kb = k_packed.transpose(0, 2, 1, 3)                       # (B,Hkv,T,hdw)
    vb = v_packed.transpose(0, 2, 1, 3)
    pc = (hd - packed_dot(qb[:, :, :, :, None, :],
                          kb[:, :, None, None, :, :], hd)) // 2  # (B,Hkv,S,G,T)
    w = softmax_weights(pc, valid[:, None, :, None, :], hd)
    bits = unpack_bits(vb, hd) > 0                            # (B,Hkv,T,hd)
    sp = exact_bits_dot(w.reshape(b, hkv, s * g, t), bits,
                        (((3,), (2,)), ((0, 1), (0, 1))))     # (B,Hkv,S*G,hd)
    out = fixed_point_out([x.reshape(b, hkv, s, g, hd) for x in sp],
                          limb_sums(w),
                          v_scale.astype(jnp.float32)[:, :, None, None, None])
    return out.transpose(0, 2, 1, 3, 4).reshape(b, s, hkv * g, hd
                                                ).astype(q.dtype)


def chunk_valid_mask(b: int, s: int, t: int, kv_len: Array, q_pos: Array,
                     window: int, causal: bool) -> Array:
    """(B, S, T) validity mask for a prefill chunk at global positions
    q_pos..q_pos+S-1 against a T-row cache with kv_len valid rows:
    t < kv_len [& t <= q_pos+i] [& t > q_pos+i-window]."""
    kpos = jnp.arange(t, dtype=jnp.int32)[None, None, :]      # (1, 1, T)
    length = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1),
                              (b,)).reshape(b, 1, 1)
    qp = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32).reshape(-1),
                          (b,)).reshape(b, 1, 1) + \
        jnp.arange(s, dtype=jnp.int32)[None, :, None]         # (B, S, 1)
    valid = jnp.broadcast_to(kpos < length, (b, s, t))
    if causal:
        valid &= kpos <= qp
    if window > 0:
        valid &= kpos > qp - window
    return valid


def prefill_attention_packed_ref(q: Array, k_packed: Array, v_packed: Array,
                                 v_scale: Array, kv_len: Array,
                                 q_pos: Array, *, window: int = 0,
                                 causal: bool = True) -> Array:
    """Oracle for kernels.prefill_attention.prefill_attention_packed.

    Chunked-prefill generalization of `decode_attention_packed_ref`: S
    float queries at global positions q_pos..q_pos+S-1 score against the
    packed cache (their own rows already written), with the causal
    triangle and optional sliding window fused into the mask:

        score_{i,t} = (hd - 2*popcount(xor(q_bits_i, k_bits_t))) / sqrt(hd)
        valid_{i,t} = t < kv_len  [& t <= q_pos+i]  [& t > q_pos+i-window]
        out_i       = v_scale * softmax(score_i)_t . sign(v_t)

    q: (B, S, Hq, hd) float; k_packed/v_packed: (B, T, Hkv, hdw) uint32;
    v_scale: (B, Hkv) float; kv_len, q_pos: scalar or (B,). With S == 1
    and q_pos == kv_len - 1 this is exactly decode_attention_packed_ref.
    The softmax runs in fixed point (packed_masked_attention_ref), so the
    kernel matches bit for bit — the tested contract, not just closeness.
    """
    b, t = k_packed.shape[0], k_packed.shape[1]
    valid = chunk_valid_mask(b, q.shape[1], t, kv_len, q_pos, window, causal)
    return packed_masked_attention_ref(q, k_packed, v_packed, v_scale, valid)


def gather_pages(pool: Array, page_table: Array) -> Array:
    """Materialize a paged cache as its contiguous equivalent.

    pool: (pool_pages, page_size, Hkv, d) — fixed-size KV pages shared by
    every slot; page_table: (B, n_pages) int32 — each row maps a slot's
    position range [i*page_size, (i+1)*page_size) to a pool page. Returns
    (B, n_pages*page_size, Hkv, d). Unallocated table entries carry the
    `pool_pages` sentinel: they clip to the last page here and the
    garbage rows are masked by cache-length masks downstream (exactly the
    t >= kv_len convention of the contiguous kernels), so paged attention
    == contiguous attention on the gathered panel, bit for bit."""
    p = pool.shape[0]
    b, np_ = page_table.shape
    idx = jnp.minimum(page_table, p - 1).reshape(-1)
    g = jnp.take(pool, idx, axis=0, mode="clip")
    return g.reshape((b, np_ * pool.shape[1]) + pool.shape[2:])


def decode_attention_packed_paged_ref(q: Array, k_pool: Array, v_pool: Array,
                                      v_scale: Array, page_table: Array,
                                      cache_len: Array, *,
                                      window: int = 0) -> Array:
    """Oracle for kernels.decode_attention.decode_attention_packed_paged:
    gather the page-table rows into a contiguous (B, T, Hkv, hdw) panel,
    then the contiguous decode oracle verbatim — the paged kernel is a
    pure addressing change, never a numerics change."""
    return decode_attention_packed_ref(
        q, gather_pages(k_pool, page_table), gather_pages(v_pool, page_table),
        v_scale, cache_len, window=window)


def prefill_attention_packed_paged_ref(q: Array, k_pool: Array, v_pool: Array,
                                       v_scale: Array, page_table: Array,
                                       kv_len: Array, q_pos: Array, *,
                                       window: int = 0,
                                       causal: bool = True) -> Array:
    """Oracle for kernels.prefill_attention.prefill_attention_packed_paged
    (gather + the contiguous chunk oracle verbatim)."""
    return prefill_attention_packed_ref(
        q, gather_pages(k_pool, page_table), gather_pages(v_pool, page_table),
        v_scale, kv_len, q_pos, window=window, causal=causal)


def binary_conv2d_ref(x: Array, w: Array) -> Array:
    """Oracle for ops.binary_conv2d: conv(sign(x), sign(w)) with SAME-size
    output and +1-valued border padding (binarized padding convention —
    sign(0) := +1, so the binary pipeline pads with +1, not 0)."""
    kh, kw, _, _ = w.shape
    ph, pw = (kh - 1) // 2, (kw - 1) // 2
    xp = jnp.pad(sign_pm1(x), ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw),
                               (0, 0)), constant_values=1.0)
    return jax.lax.conv_general_dilated(
        xp, sign_pm1(w), (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(jnp.float32)


def selective_scan_ref(dt: Array, xi: Array, bmat: Array, cmat: Array,
                       a_mat: Array) -> tuple[Array, Array]:
    """Oracle for kernels.selective_scan: sequential diagonal recurrence
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ; y_t = C_t . h_t."""
    def step(h, xs):
        dt_t, xi_t, b_t, c_t = xs
        a = jnp.exp(dt_t[..., None] * a_mat)
        h = a * h + (dt_t * xi_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    b, t, d = dt.shape
    h0 = jnp.zeros((b, d, a_mat.shape[-1]), jnp.float32)
    h, ys = jax.lax.scan(
        step, h0, (dt.swapaxes(0, 1).astype(jnp.float32),
                   xi.swapaxes(0, 1).astype(jnp.float32),
                   bmat.swapaxes(0, 1).astype(jnp.float32),
                   cmat.swapaxes(0, 1).astype(jnp.float32)))
    return ys.swapaxes(0, 1), h


def pack_operands(x: Array, w: Array) -> tuple[Array, Array, int]:
    """Pack (M, K) lhs and (K, N) rhs into the kernel wire format."""
    k = x.shape[-1]
    assert w.shape[0] == k
    return pack_bits(x), pack_bits(w.T), k
