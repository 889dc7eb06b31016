"""Bit-packing for {-1,+1} tensors.

Convention: bit 1 <-> +1, bit 0 <-> -1, packed little-endian along the last
axis into uint32 words (lane dim K -> K/32 words). With this convention a
K-length +-1 dot product is

    dot(a, b) = K - 2 * popcount(xor(a_bits, b_bits))

because xor is 1 exactly where the signs differ. Padding: the last word is
padded with 1-bits in *both* operands so xor(pad, pad) = 0 contributes
nothing; the true K must be supplied to the dot formula.

This is the storage/compute format for the Pallas binary GEMM, the packed
FSDP all-gather, and the 1-bit checkpoint format.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
WORD = 32


def packed_width(k: int) -> int:
    return (k + WORD - 1) // WORD


def pack_bits(x: Array) -> Array:
    """Pack a +-1 (or any sign-carrying) tensor along its last axis.

    (..., K) float -> (..., ceil(K/32)) uint32. Pad bits are 1 (i.e. +1).
    """
    k = x.shape[-1]
    kw = packed_width(k)
    pad = kw * WORD - k
    bits = (x >= 0)
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.ones(x.shape[:-1] + (pad,), dtype=bits.dtype)], axis=-1
        )
    bits = bits.reshape(x.shape[:-1] + (kw, WORD)).astype(jnp.int32)
    weights = jnp.left_shift(jnp.int32(1), jnp.arange(WORD, dtype=jnp.int32))
    # the bits are disjoint, so the int32 sum is their OR; bitcast gives the
    # uint32 word (no unsigned reduction, which the TPU compiler lacks)
    return jax.lax.bitcast_convert_type(
        jnp.sum(bits * weights, axis=-1, dtype=jnp.int32), jnp.uint32)


def unpack_bits(p: Array, k: int, dtype=jnp.float32) -> Array:
    """Inverse of pack_bits: (..., ceil(K/32)) uint32 -> (..., K) +-1."""
    kw = p.shape[-1]
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (p[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(p.shape[:-1] + (kw * WORD,))[..., :k]
    return (flat.astype(dtype) * 2 - 1)


def packed_dot(a_p: Array, b_p: Array, k: int) -> Array:
    """dot over the packed word axis (last axis of both): K - 2*popcount(xor).

    a_p: (..., KW) uint32, b_p: (..., KW) uint32 with broadcastable prefixes.
    Returns int32.
    """
    x = jax.lax.population_count(jnp.bitwise_xor(a_p, b_p))
    return jnp.int32(k) - 2 * jnp.sum(x.astype(jnp.int32), axis=-1)


def packed_nbytes(shape: tuple[int, ...]) -> int:
    """Bytes needed to store a +-1 tensor of `shape` packed (last axis)."""
    return int(np.prod(shape[:-1], dtype=np.int64)) * packed_width(shape[-1]) * 4
