"""Pallas kernel: fused binarize + bit-pack.

Packs sign bits of a float tensor into uint32 words in one VMEM pass —
the producer side of every binary-GEMM / packed-checkpoint / packed-
collective path. Fusing avoids materializing the intermediate +-1 float
tensor to HBM (2x-4x traffic at the binarization boundary).

Layout matches repro.core.bitpack: bit 1 <-> (x >= 0), little-endian
along the last axis, 32 values per word.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitpack import WORD, packed_width

Array = jax.Array


def pack_lanes(bits: Array) -> Array:
    """(R, 32*W) bool -> (R, W) uint32 wire words, packed along the lanes
    on the MXU: word j = sum_i bit[32j+i] << i is a matmul against a
    block-diagonal matrix of powers of two. Done as two 16-bit halves so
    every partial sum (< 2^16) is exact in the f32 accumulator and every
    operand (0/1 bits, powers of two) is exact in bf16. The TPU compiler
    cannot split the lane dim into (W, 32), which is how
    `bitpack.pack_bits` packs outside a kernel."""
    n = bits.shape[-1]
    x = jnp.where(bits, 1.0, 0.0).astype(jnp.bfloat16)
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n // WORD), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n // WORD), 1)
    bit = row % WORD
    own = (row // WORD) == col

    def half(high: bool) -> Array:
        p = jnp.where(own & ((bit >= WORD // 2) == high),
                      jnp.left_shift(1, bit % (WORD // 2)), 0)
        p = p.astype(jnp.float32).astype(jnp.bfloat16)
        return jnp.dot(x, p, preferred_element_type=jnp.float32
                       ).astype(jnp.int32)

    return jax.lax.bitcast_convert_type(half(False) | (half(True) << 16),
                                        jnp.uint32)


def _pack_kernel(x_ref, o_ref):
    """x_ref: (bm, bkw*32) float; o_ref: (bm, bkw) uint32."""
    o_ref[...] = pack_lanes(x_ref[...].astype(jnp.float32) >= 0)


def pack_bits_kernel(x: Array, *, bm: int = 256, bkw: int = 8,
                     interpret: bool | None = None) -> Array:
    """(M, K) float -> (M, ceil(K/32)) uint32, pad bits = 1 (i.e. +1)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = x.shape
    kw = packed_width(k)
    pad_k = kw * WORD - k
    if pad_k:
        x = jnp.pad(x, ((0, 0), (0, pad_k)), constant_values=1.0)
    bm = min(bm, m)
    bkw = min(bkw, kw)
    pm, pw = (-m) % bm, (-kw) % bkw
    if pm or pw:
        x = jnp.pad(x, ((0, pm), (0, pw * WORD)), constant_values=1.0)
    gm, gw = x.shape[0] // bm, (x.shape[1] // WORD) // bkw

    out = pl.pallas_call(
        _pack_kernel,
        grid=(gm, gw),
        in_specs=[pl.BlockSpec((bm, bkw * WORD), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bkw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], x.shape[1] // WORD),
                                       jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x)
    return out[:m, :kw]
