"""Binary (XNOR+popcount) GEMM: Pallas TPU kernels + per-shape dispatch.

TPU-native adaptation of the paper's CUDA binary GEMM (DESIGN.md §4).
This module hosts every realization of `sign(x) @ sign(w)` and the
dispatch layer that picks between them per shape:

  * `binary_gemm_vpu` — operands bit-packed along K into uint32 words
    (wire format of repro.core.bitpack). The kernel tiles (bm, bn) output
    blocks into VMEM, streams (bm, bk)/(bn, bk) word-tiles, and
    accumulates popcount(xor(a, b)) on the VPU's 8x128 int lanes (the
    honest analogue of __popc-based SIMT kernels). `uk` is how many K-words
    the inner loop unrolls per step — uk=0 unrolls the whole tile.

  * `binary_gemm_mxu` — fused binarize-then-matmul: float tiles are
    sign-quantized to +-1 bf16 *in VMEM* and fed to the MXU's 128x128
    systolic array. The bitwise formulation and the MXU formulation
    compute the same exact integers; which one wins is a per-shape
    question (large N favors the MXU — roofline discussion in
    EXPERIMENTS.md), which is exactly what the dispatch layer decides.

  * `binary_gemm_vpu_packed_io` — the bit-resident serving kernel: packed
    (or first-layer float) lhs against frozen packed weights, with the
    whole inter-layer epilogue fused: dot = K - 2*acc, per-channel int32
    threshold compare (inference BN/shift-BN/bias + sign folded at freeze
    time, core.packed.fold_*_sign_threshold), and the N-axis bitpack.
    Output is (M, ceil(N/32)) uint32 in the wire format, so the next
    binary layer consumes it directly.

  * `dispatch_binary_gemm` / `dispatch_binary_gemm_fused` — the route
    pickers callers actually use (ops.packed_matmul{,_fused} default to
    them). Routes: 'vpu' (popcount Pallas kernel, block shapes from the
    tuning cache), 'mxu' (±1-bf16 dot_general), 'xla' (the packed
    popcount formulation lowered by XLA — on hosts where Pallas runs in
    interpret mode this is the fast packed path), and 'float' (±1 f32
    matmul fallback; exact, since ±1 dots are small integers). The
    winner per (kernel, shape bucket, backend) comes from
    `repro.kernels.tune`'s persisted cache; every route is bit-exact
    with `ref.binary_matmul_packed_ref` (asserted in tests and at tune
    time), so dispatch can never change results, only microseconds.

Block shapes are multiples of (8, 128) for VPU register tiling and 128x128
for the MXU. Grids iterate K innermost ("arbitrary") so output blocks are
revisited for accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitpack import WORD, pack_bits, unpack_bits
from repro.core.packed import ALWAYS_THRESH
from repro.kernels import ref
from repro.kernels._geometry import fused_gemm_geometry, gemm_geometry
from repro.kernels.pack import pack_lanes

Array = jax.Array


def _i32(x: Array) -> Array:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _popcount_outer(a_words: Array, b_words: Array, acc: Array, at_ref,
                    bt_ref, uk: int) -> Array:
    """acc (bm, bn) += sum_w popcount(xor(a[:, w], b[:, w])) — the XNOR
    inner product over (bm, bk) x (bn, bk) word tiles.

    Both tiles are transposed into VMEM scratch once, so each K-word is a
    row (1, bn) of b and a column (bm, 1) of a: one xor, popcount and add
    over the (bm, bn) accumulator per word, all on the VPU lanes. `uk` is
    the number of words unrolled per step of the inner loop: 0 (or >= bk)
    unrolls the whole tile; otherwise a fori_loop runs bk // uk steps. All
    variants are exact — integer adds commute — so uk is purely a
    performance knob for the autotuner.
    """
    at_ref[...] = _i32(a_words).T
    bt_ref[...] = _i32(b_words).T
    bk = bt_ref.shape[0]

    def word(w, acc):
        a = at_ref[pl.ds(w, 1), :].T                         # (bm, 1)
        return acc + jax.lax.population_count(a ^ bt_ref[pl.ds(w, 1), :])

    if uk <= 0 or uk >= bk:
        for w in range(bk):
            acc = word(w, acc)
        return acc

    def step(c, acc):
        for i in range(uk):
            acc = word(c * uk + i, acc)
        return acc

    return jax.lax.fori_loop(0, bk // uk, step, acc)


def _word_scratch(bm: int, bn: int, bk: int) -> list:
    return [pltpu.VMEM((bk, bm), jnp.int32), pltpu.VMEM((bk, bn), jnp.int32)]


# ---------------------------------------------------------------------------
# VPU popcount kernel over packed uint32 words
# ---------------------------------------------------------------------------
def _vpu_kernel(a_ref, b_ref, o_ref, at_ref, bt_ref, *, k_true: int, nk: int,
                uk: int, pack_lhs: bool):
    """a_ref: (bm, bk) uint32 — or (bm, bk*32) float when `pack_lhs`,
    sign-packed here in VMEM; b_ref: (bn, bk) uint32; o_ref: (bm, bn)
    int32, revisited across the K grid axis as the accumulator."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]
    aw = pack_lanes(a.astype(jnp.float32) >= 0) if pack_lhs else a
    acc = _popcount_outer(aw, b_ref[...], o_ref[...], at_ref, bt_ref, uk)
    is_last = pl.program_id(2) == nk - 1
    # fold the K - 2*acc epilogue into the final K-step
    o_ref[...] = jnp.where(is_last, jnp.int32(k_true) - 2 * acc, acc)


def _vpu_call(a: Array, b_packed: Array, k_true: int, geo, *,
              pack_lhs: bool, interpret: bool) -> Array:
    ka = geo.bk * WORD if pack_lhs else geo.bk
    return pl.pallas_call(
        functools.partial(_vpu_kernel, k_true=k_true, nk=geo.gk, uk=geo.uk,
                          pack_lhs=pack_lhs),
        grid=(geo.gm, geo.gn, geo.gk),
        in_specs=[
            pl.BlockSpec((geo.bm, ka), lambda i, j, k: (i, k)),
            pl.BlockSpec((geo.bn, geo.bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((geo.bm, geo.bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a.shape[0], b_packed.shape[0]),
                                       jnp.int32),
        scratch_shapes=_word_scratch(geo.bm, geo.bn, geo.bk),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="binary_gemm_vpu",
    )(a, b_packed)


def binary_gemm_vpu(a_packed: Array, b_packed: Array, k_true: int, *,
                    bm: int = 128, bn: int = 128, bk: int = 8, uk: int = 1,
                    interpret: bool | None = None) -> Array:
    """XNOR-popcount GEMM. a_packed: (M, KW) uint32, b_packed: (N, KW)
    uint32 (rhs pre-transposed + packed). Returns (M, N) int32 =
    sign-dot over the original K (pad bits cancel in xor)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, kw = a_packed.shape
    n, kw2 = b_packed.shape
    assert kw == kw2, (kw, kw2)
    geo = gemm_geometry(m, n, kw, bm, bn, bk, uk, aligned=not interpret)
    # pad with identical words so xor(pad, pad) == 0 in the K direction;
    # M/N padding rows are sliced off after the call.
    if geo.pm or geo.pk:
        a_packed = jnp.pad(a_packed, ((0, geo.pm), (0, geo.pk)))
    if geo.pn or geo.pk:
        b_packed = jnp.pad(b_packed, ((0, geo.pn), (0, geo.pk)))
    out = _vpu_call(a_packed, b_packed, k_true, geo, pack_lhs=False,
                    interpret=interpret)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# VPU popcount kernel with a pre-packed weight operand: the serving path.
# Weights were frozen to wire-format words at load time (core.packed), so
# only the float activations get sign-packed here — in VMEM, fused with the
# xor/popcount accumulation, never materializing packed activations to HBM.
# ---------------------------------------------------------------------------
def binary_gemm_vpu_packed(a: Array, b_packed: Array, k_true: int, *,
                           bm: int = 128, bn: int = 128, bk: int = 8,
                           uk: int = 1,
                           interpret: bool | None = None) -> Array:
    """XNOR-popcount GEMM against frozen packed weights.

    a: (M, K) float activations; b_packed: (N, ceil(K/32)) uint32 — the rhs
    already transposed + packed once at freeze time (core.packed wire
    format, pad bits 1). Returns (M, N) int32 = sign(a) . sign-rows(b).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = a.shape
    n, kw = b_packed.shape
    assert k == k_true and kw * 32 >= k, (k, k_true, kw)
    # pad a's K up to full words with +1.0: bit 1 matches the wire-format
    # pad bits of b, so xor(pad, pad) == 0 contributes nothing
    if kw * 32 - k:
        a = jnp.pad(a, ((0, 0), (0, kw * 32 - k)), constant_values=1.0)
    geo = gemm_geometry(m, n, kw, bm, bn, bk, uk, aligned=not interpret)
    # word-granular K padding: b grows zero words; a grows -1.0 columns,
    # which pack to the zero word, so xor(0, 0) == 0 again cancels.
    if geo.pm or geo.pk:
        a = jnp.pad(a, ((0, geo.pm), (0, geo.pk * 32)), constant_values=-1.0)
    if geo.pn or geo.pk:
        b_packed = jnp.pad(b_packed, ((0, geo.pn), (0, geo.pk)))
    out = _vpu_call(a, b_packed, k_true, geo, pack_lhs=True,
                    interpret=interpret)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Bit-resident kernel: packed-I/O GEMM with the fused BN+sign+repack epilogue.
#
# The lhs is either already wire-format words (every binary layer after the
# first) or floats sign-packed in VMEM (the chain entry). The epilogue never
# leaves VMEM: dot = K - 2*acc, then bit_n = (dot >= t_n) XOR flip_n — the
# per-channel int32 threshold that core.packed folds from inference-time
# BN / shift-BN / bias + sign at freeze time — then the bits repack along N
# into uint32 words. Inter-layer activation traffic drops from 4 bytes/unit
# (int32) to 1 bit/unit.
#
# K is kept whole per block (KW = K/32 words is small by construction), so
# the grid is (M, N)-parallel only and no cross-step accumulator state is
# needed.
# ---------------------------------------------------------------------------
def _fused_epilogue_kernel(a_ref, b_ref, t_ref, f_ref, o_ref, at_ref, bt_ref,
                           *, k_true: int, packed_lhs: bool, uk: int):
    """a_ref: (bm, kw) uint32 | (bm, kw*32) float; b_ref: (bn, kw) uint32;
    t_ref/f_ref: (1, bn) int32; o_ref: (bm, bn//32) uint32."""
    a = a_ref[...]
    aw = a if packed_lhs else pack_lanes(a.astype(jnp.float32) >= 0)
    acc = jnp.zeros((aw.shape[0], b_ref.shape[0]), jnp.int32)
    acc = _popcount_outer(aw, b_ref[...], acc, at_ref, bt_ref, uk)
    dot = jnp.int32(k_true) - 2 * acc
    o_ref[...] = pack_lanes((dot >= t_ref[...]) != (f_ref[...] != 0))


def binary_gemm_vpu_packed_io(a: Array, b_packed: Array, thresh: Array,
                              flip: Array, k_true: int, *, bm: int = 128,
                              bn: int = 128, uk: int = 1,
                              interpret: bool | None = None) -> Array:
    """XNOR-popcount GEMM whose epilogue emits wire-format sign words.

    a: (M, KW) uint32 packed lhs (wire format, pad bits 1) or (M, K) float
    (chain entry: sign-packed in VMEM). b_packed: (N, KW) uint32 frozen
    weights. thresh/flip: (N,) int32 — bit_n = (dot_n >= thresh_n) XOR
    flip_n. Returns (M, ceil(N/32)) uint32 whose pad bits are 1 (+1), i.e.
    exactly the lhs operand of the next binary layer.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    packed_lhs = a.dtype == jnp.uint32
    n, kw = b_packed.shape
    assert thresh.shape == (n,) and flip.shape == (n,), (thresh.shape, n)
    m = a.shape[0]
    if packed_lhs:
        assert a.shape[1] == kw, (a.shape, kw)
    else:
        assert a.shape[1] == k_true and kw * WORD >= k_true, (a.shape, k_true)
        # pad lhs K up to full words with +1.0 — matches the wire-format pad
        # bits of b, so xor(pad, pad) == 0 contributes nothing
        if kw * WORD - k_true:
            a = jnp.pad(a, ((0, 0), (0, kw * WORD - k_true)),
                        constant_values=1.0)
    geo = fused_gemm_geometry(m, n, kw, bm, bn, uk, aligned=not interpret)
    if geo.pm:
        a = jnp.pad(a, ((0, geo.pm), (0, 0)),
                    constant_values=0 if packed_lhs else -1.0)
    if geo.pn:
        b_packed = jnp.pad(b_packed, ((0, geo.pn), (0, 0)))
        # padded output channels must emit bit 1 (+1): that is the wire
        # format's pad convention, which the next layer's weight pad bits
        # cancel against. ALWAYS_THRESH makes (dot >= t) always true.
        thresh = jnp.pad(thresh, (0, geo.pn), constant_values=ALWAYS_THRESH)
        flip = jnp.pad(flip, (0, geo.pn))
    bm, bn = geo.bm, geo.bn

    out = pl.pallas_call(
        functools.partial(_fused_epilogue_kernel, k_true=k_true,
                          packed_lhs=packed_lhs, uk=geo.uk),
        grid=(geo.gm, geo.gn),
        in_specs=[
            pl.BlockSpec((bm, kw if packed_lhs else kw * WORD),
                         lambda i, j: (i, 0)),
            pl.BlockSpec((bn, kw), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn // WORD), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (a.shape[0], b_packed.shape[0] // WORD), jnp.uint32),
        scratch_shapes=_word_scratch(bm, bn, kw),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret, name="binary_gemm_fused",
    )(a, b_packed, thresh[None, :], flip[None, :])
    return out[:m, :(n + WORD - 1) // WORD]


# ---------------------------------------------------------------------------
# MXU fused binarize + matmul kernel (float in, +-1 bf16 on the MXU)
# ---------------------------------------------------------------------------
def _mxu_kernel(x_ref, w_ref, o_ref, *, nk: int):
    """x_ref: (bm, bk) f32, w_ref: (bk, bn) f32, o_ref: (bm, bn) f32."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xb = jnp.where(x_ref[...] >= 0, 1.0, -1.0).astype(jnp.bfloat16)
    wb = jnp.where(w_ref[...] >= 0, 1.0, -1.0).astype(jnp.bfloat16)
    o_ref[...] += jnp.dot(xb, wb, preferred_element_type=jnp.float32)


def binary_gemm_mxu(x: Array, w: Array, *, bm: int = 128, bn: int = 128,
                    bk: int = 512, interpret: bool | None = None) -> Array:
    """Fused sign-quantize + MXU matmul. x: (M, K) float, w: (K, N) float.
    Returns (M, N) float32 == sign(x) @ sign(w)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    geo = gemm_geometry(m, n, k, bm, bn, bk, aligned=not interpret)
    if geo.pm or geo.pk:
        # K padding scheme: pad x's K-cols AND w's K-rows with +1.0, so each
        # pad position contributes sign(+1)*sign(+1) = +1 to every dot;
        # subtract the constant pk from the output afterwards. (M/N padding
        # rows/cols are simply sliced off.)
        x = jnp.pad(x, ((0, geo.pm), (0, geo.pk)), constant_values=1.0)
    if geo.pn or geo.pk:
        w = jnp.pad(w, ((0, geo.pk), (0, geo.pn)), constant_values=1.0)

    out = pl.pallas_call(
        functools.partial(_mxu_kernel, nk=geo.gk),
        grid=(geo.gm, geo.gn, geo.gk),
        in_specs=[
            pl.BlockSpec((geo.bm, geo.bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((geo.bk, geo.bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((geo.bm, geo.bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], w.shape[1]), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="binary_gemm_mxu",
    )(x, w)
    if geo.pk:
        out = out - jnp.float32(geo.pk)  # remove the +1*+1 pad contributions
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Dispatch: one entry point per GEMM flavor; the route and its block
# parameters come from the tuning cache (repro.kernels.tune), so call
# sites stop hardcoding 'vpu' vs 'mxu' vs fallback per shape.
# ---------------------------------------------------------------------------
def dispatch_binary_gemm(a: Array, b_packed: Array, k_true: int, *,
                         route: str | None = None,
                         interpret: bool | None = None, **params) -> Array:
    """Packed-rhs binary GEMM with per-shape route selection.

    a: (M, K) float activations or (M, KW) uint32 wire-format lhs;
    b_packed: (N, KW) uint32 frozen weights. Returns (M, N) int32, the
    exact sign-dot — every route computes identical integers (the float
    and MXU routes sum ±1 products, which are exact in f32 for any
    realistic K), so the route is invisible to callers.

    route=None consults `tune.get_route('binary_gemm', ...)`; an explicit
    route (+ block params) bypasses the cache — tests and the autotuner
    use that to pin candidates.
    """
    packed_lhs = a.dtype == jnp.uint32
    m = a.shape[0]
    n, kw = b_packed.shape
    if route is None:
        from repro.kernels import tune
        # pl keys the cache on the lhs form: packed lhs runs binary_gemm_vpu
        # while float lhs runs the in-kernel-pack binary_gemm_vpu_packed —
        # different kernels, so they are tuned (and cached) separately.
        route, tuned = tune.get_route("binary_gemm", m=m, n=n, kw=kw,
                                      pl=int(packed_lhs))
        params = {**tuned, **params}
    if route == "vpu":
        if packed_lhs:
            return binary_gemm_vpu(a, b_packed, k_true, interpret=interpret,
                                   **params)
        return binary_gemm_vpu_packed(a, b_packed, k_true,
                                      interpret=interpret, **params)
    if route == "xla":
        aw = a if packed_lhs else pack_bits(a)
        return ref.binary_matmul_packed_ref(aw, b_packed, k_true)
    if route == "float":
        x = unpack_bits(a, k_true) if packed_lhs else ref.sign_pm1(a)
        w = unpack_bits(b_packed, k_true)                    # (N, K) ±1
        return jnp.matmul(x, w.T).astype(jnp.int32)
    if route == "mxu":
        x = unpack_bits(a, k_true) if packed_lhs else a
        w = unpack_bits(b_packed, k_true)                    # (N, K) ±1
        return binary_gemm_mxu(x, w.T, interpret=interpret,
                               **params).astype(jnp.int32)
    raise ValueError(f"unknown binary_gemm route: {route}")


def dispatch_binary_gemm_fused(a: Array, b_packed: Array, thresh: Array,
                               flip: Array, k_true: int, *,
                               route: str | None = None,
                               interpret: bool | None = None,
                               **params) -> Array:
    """Fused-epilogue binary GEMM (bit-resident chain step) with per-shape
    route selection. Same contract as `binary_gemm_vpu_packed_io` —
    returns (M, ceil(N/32)) uint32 wire-format words — with the route
    ('vpu' Pallas kernel / 'xla' packed formulation / 'float' ±1 matmul
    feeding the identical threshold+repack epilogue) resolved from the
    tuning cache. All routes are bit-exact vs `ref.binary_matmul_fused_ref`.
    """
    packed_lhs = a.dtype == jnp.uint32
    m = a.shape[0]
    n, kw = b_packed.shape
    if route is None:
        from repro.kernels import tune
        route, tuned = tune.get_route("binary_gemm_fused", m=m, n=n, kw=kw,
                                      pl=int(packed_lhs))
        params = {**tuned, **params}
    if route == "vpu":
        return binary_gemm_vpu_packed_io(a, b_packed, thresh, flip, k_true,
                                         interpret=interpret, **params)
    if route == "xla":
        aw = a if packed_lhs else pack_bits(a)
        return ref.binary_matmul_fused_ref(aw, b_packed, thresh, flip, k_true)
    if route == "float":
        x = unpack_bits(a, k_true) if packed_lhs else ref.sign_pm1(a)
        w = unpack_bits(b_packed, k_true)                    # (N, K) ±1
        ints = jnp.matmul(x, w.T).astype(jnp.int32)
        bits = (ints >= thresh[None, :]) != (flip[None, :] != 0)
        return pack_bits(jnp.where(bits, 1.0, -1.0))
    raise ValueError(f"unknown binary_gemm_fused route: {route}")
