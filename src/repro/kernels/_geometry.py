"""Cached grid/block geometry for the packed Pallas kernels.

Every kernel entry point used to re-derive its block clamps and pad
amounts (`min(bm, m)`, `(-m) % bm`, grid divisions) inline on every call
— once per trace per call site. The helpers here compute that geometry
exactly once per distinct (shape, block) tuple and memoize it
(`functools.lru_cache`), so repeated traces of the serving step hit a
dict lookup, and the GEMM and attention kernels share one definition of
the clamping/padding rules instead of three hand-copied variants.

All inputs and outputs are plain Python ints (static shapes), never
traced values — the cache key is hashable by construction and the
results feed BlockSpecs/grids, which must be static anyway.

`aligned=True` (every kernel passes `not interpret`) also applies the TPU
compiler's block rule: the last two dims of every block are multiples of
(8, 128) or span the whole array dim. A proposed tile that breaks it is
rounded up to the next legal edge (or to the whole dim). Interpret mode
has no such rule, so the CPU tests keep exercising ragged tiles.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

from repro.core.bitpack import WORD


SUBLANE, LANE = 8, 128


def _fit(block: int, dim: int, align: int) -> int:
    """Clamp a proposed block edge to `dim`; a partial block is rounded up
    to a multiple of `align` (1 = any edge), or to the whole dim."""
    if block >= dim:
        return dim
    block = -(-block // align) * align
    return dim if block >= dim else block


class GemmGeometry(NamedTuple):
    """Clamped blocks, pad amounts, and grid for an (M, N, KW) word GEMM."""
    bm: int
    bn: int
    bk: int
    uk: int          # words per inner popcount step (0/bk = whole block)
    pm: int          # M rows of padding
    pn: int          # N rows of padding
    pk: int          # K words of padding
    gm: int
    gn: int
    gk: int


@functools.lru_cache(maxsize=None)
def gemm_geometry(m: int, n: int, kw: int, bm: int, bn: int, bk: int,
                  uk: int = 1, *, aligned: bool = False) -> GemmGeometry:
    """Geometry for binary_gemm_vpu{,_packed} (and the MXU kernel, with
    kw the float K): blocks clamped to the operand, pads up to block
    multiples, grid sizes, and the inner-loop word-chunk width `uk`
    clamped to divide bk. Aligned: bm is a sublane edge (lhs/out rows),
    bn and bk are lane edges (out columns, word columns)."""
    sub, lane = (SUBLANE, LANE) if aligned else (1, 1)
    bm, bn, bk = _fit(bm, m, sub), _fit(bn, n, lane), _fit(bk, kw, lane)
    uk = min(uk, bk) if uk > 0 else 0
    if uk > 0:
        while bk % uk:           # uk must tile bk exactly
            uk -= 1
    pm, pn, pk = (-m) % bm, (-n) % bn, (-kw) % bk
    return GemmGeometry(bm, bn, bk, uk, pm, pn, pk,
                        (m + pm) // bm, (n + pn) // bn, (kw + pk) // bk)


@functools.lru_cache(maxsize=None)
def fused_gemm_geometry(m: int, n: int, kw: int, bm: int, bn: int,
                        uk: int = 0, *, aligned: bool = False) -> GemmGeometry:
    """Geometry for binary_gemm_vpu_packed_io: K stays whole per block
    (bk == kw), bn is clamped to a multiple of 32 (the N-axis repack
    width), and `uk` is clamped to a divisor of kw — the fused kernel's
    inner fori_loop runs kw//uk steps, so a non-divisor uk would silently
    drop the kw%uk trailing words (same rule gemm_geometry applies to
    uk vs bk). Aligned: the output block is (bm, bn/32) words, so a
    partial bn is a multiple of 32 * 128 bits."""
    assert bn % WORD == 0, f"bn must be a multiple of {WORD} (N repack): {bn}"
    bm = _fit(bm, m, SUBLANE if aligned else 1)
    bn = _fit(bn, ((n + WORD - 1) // WORD) * WORD,
              WORD * LANE if aligned else 1)
    uk = min(uk, kw) if uk > 0 else 0
    if uk > 0:
        while kw % uk:           # uk must tile the whole-K block exactly
            uk -= 1
    pm, pn = (-m) % bm, (-n) % bn
    return GemmGeometry(bm, bn, kw, uk, pm, pn, 0,
                        (m + pm) // bm, (n + pn) // bn, 1)


class AttnGeometry(NamedTuple):
    """Clamped blocks, pads, and grid axes for the packed attention
    kernels' (batch-row, query-row) tiling."""
    bb: int          # batch rows per program
    bq: int          # query rows per program
    pb: int          # batch rows of padding
    ps: int          # query rows of padding
    gb: int          # grid size along batch
    gs: int          # grid size along query rows


@functools.lru_cache(maxsize=None)
def attn_geometry(b: int, s: int, block_b: int, block_q: int, *,
                  group: int = 1, aligned: bool = False) -> AttnGeometry:
    """Shared decode/prefill attention geometry. Decode passes s == 1,
    block_q == 1; prefill tiles both axes. A query block holds
    block_q * group score rows (GQA heads ride along); aligned, a partial
    block holds a multiple of 8 of them."""
    bb = max(1, min(block_b, b))
    step = SUBLANE // math.gcd(SUBLANE, group) if aligned else 1
    bq = _fit(max(1, block_q), s, step)
    pb, ps = (-b) % bb, (-s) % bq
    return AttnGeometry(bb, bq, pb, ps, (b + pb) // bb, (s + ps) // bq)


# What the paged attention kernel may hold in VMEM: its panels and its
# double-buffered blocks, below the TPU's 16 MiB scoped default
ATTN_VMEM_BUDGET = 12 << 20


@functools.lru_cache(maxsize=None)
def attn_head_block(hkv: int, hdw: int, bb: int, rows: int, hd: int,
                    n_pos: int, *, aligned: bool = False) -> int:
    """kv heads per program of the paged attention kernel: the most that
    divide hkv and keep its VMEM — two (bb, n_pos) panels of hb heads'
    hdw words, and the double-buffered query, V-sum and weight-sum
    blocks, each (rows, ·) tile padded to whole (8, 128) tiles when
    aligned — within ATTN_VMEM_BUDGET (one head, if none fits)."""
    sub, lane = (SUBLANE, LANE) if aligned else (1, 1)

    def up(x: int, m: int) -> int:
        return -(-x // m) * m

    def need(hb: int) -> int:
        panels = 2 * bb * up(hdw, sub) * n_pos
        blocks = 2 * bb * up(rows, sub) * (
            up(hdw, lane) + 2 * up(hd, lane) + 2 * up(1, lane))
        return 4 * hb * (panels + blocks)

    fits = [hb for hb in range(1, hkv + 1)
            if hkv % hb == 0 and need(hb) <= ATTN_VMEM_BUDGET]
    return max(fits, default=1)


class ShardGeometry(NamedTuple):
    """One tensor-parallel axis split: `dim` rows over `parts` devices."""
    dim: int
    parts: int
    local: int       # rows per device


@functools.lru_cache(maxsize=None)
def shard_geometry(dim: int, parts: int, *, name: str = "dim",
                   multiple: int = 1) -> ShardGeometry:
    """Validated geometry for sharding one kernel axis over a mesh axis.

    The packed kernels' grids are derived from *local* shard shapes under
    shard_map, so the split must be exact: `dim % parts == 0` (no ragged
    shards) and each local extent a multiple of `multiple` — the fused
    GEMM's output words repack 32 N-columns per uint32, so its N shard
    must stay word-aligned or the per-device word axes would not
    concatenate into the unsharded layout.
    """
    assert parts >= 1, parts
    assert dim % parts == 0, \
        f"{name}={dim} does not divide over {parts} mesh devices"
    local = dim // parts
    assert local % multiple == 0, \
        f"{name} shard of {local} rows breaks the required multiple " \
        f"of {multiple} (dim={dim}, parts={parts})"
    return ShardGeometry(dim, parts, local)
