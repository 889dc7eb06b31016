"""Chunked-prefill attention over a bit-resident KV cache: Pallas kernel
+ dispatch.

The prefill-side complement of `decode_attention_packed`: PR 4 made every
*decode* step read only uint32 sign bitplanes, but admission still ran a
whole prompt through float flash attention in one head-of-line-blocking
call. With chunked prefill (serving.scheduler, prefill_chunk > 0) a prompt
advances one fixed-shape chunk at a time, and the cross-chunk attention —
a chunk of float queries against everything already written to the packed
cache, plus the chunk's own causal triangle — is exactly this kernel:

  * scores: the query chunk is sign-packed once and XOR'd against each
    packed K row, popcounted on the VPU lanes — `q.k = hd - 2*popcount`
    — never unpacking K. The chunk's own K rows are written to the cache
    *before* the call, so intra-chunk (triangle) and cross-chunk scores
    come out of the same packed panel;
  * masking: per-row valid length `kv_len` (everything written so far,
    current chunk included), the causal triangle `t <= q_pos + i`, and an
    optional sliding window, all fused in VMEM. `causal=False` drops the
    triangle (VLM cross-attention against packed image KV);
  * softmax: the scores are integers, so the softmax numerators are
    30-bit fixed-point integers (`ref.softmax_weights`), computed in VMEM;
  * V accumulation: packed V unpacks to 0/1 bits in VMEM only and is summed
    under the weights on the MXU, exactly (`ref.exact_bits_dot`); the
    caller scales by the per-head fp `v_scale` (`ref.fixed_point_out`).

Grid is (B/block_b, Hkv, S/block_q): each program owns `block_b` batch
rows of one (kv head, query sub-chunk) and streams the full K/V panels
through VMEM — T*hdw words is ~1/32 of the float K/V a flash-attention
prefill of the same chunk would read. The panels are laid out (hdw, T),
positions on the lanes, so the scores, the masks and the softmax are
lane-dense (B, rows, T) tiles and no block has a lane dim of 1-4 words.
Both block sizes are autotuned knobs (repro.kernels.tune): block_q trades
triangle waste against per-program overhead, block_b amortizes that
overhead across batch rows. GQA query heads ride in the same block.

Decode is this kernel with S == 1 and q_pos == kv_len - 1
(`kernels.decode_attention` calls `packed_attention` that way), so one
kernel body serves decode and prefill, contiguous and paged.

`prefill_attention_packed` is the dispatching entry point: `route=None`
consults the tuning cache, which may pick this Pallas kernel ('pallas',
with tuned block_q/block_b) or the XLA-lowered packed formulation ('xla',
the oracle itself — the fast packed path on hosts where Pallas runs in
interpret mode). Semantics are defined by
`repro.kernels.ref.prefill_attention_packed_ref`. Every sum the kernel
takes is an exact integer sum, so it matches the oracle bit for bit
whatever order either reduces in (tests/test_prefill_attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitpack import WORD, pack_bits
from repro.kernels import ref
from repro.kernels._geometry import LANE, attn_geometry

Array = jax.Array


def _i32(x: Array) -> Array:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def packed_scores(qb: Array, kt: Array) -> Array:
    """Popcount of xor(query words, key words) summed over head_dim words.
    qb (bb, R, hdw) uint32 query rows; kt (bb, hdw, T) uint32 key panel.
    Returns (bb, R, T) int32; the sign dot is hd - 2 * this."""
    acc = jnp.zeros((qb.shape[0], qb.shape[1], kt.shape[2]), jnp.int32)
    for w in range(kt.shape[1]):
        acc += jax.lax.population_count(_i32(qb[:, :, w:w + 1])
                                        ^ _i32(kt[:, w:w + 1, :]))
    return acc


def _attend(qb, kt, vt, lens, qpos, q_off, *, hd: int, group: int,
            window: int, causal: bool):
    """Shared attention core: qb (bb, R, hdw) uint32 query rows (row r is
    query q_off + r // group, GQA head r % group), kt/vt (bb, hdw, T)
    uint32 panels, lens/qpos (bb, 1, 1) int32. Returns the exact integer
    limb sums (ref.exact_bits_dot's (bb, R, hd) pair, ref.limb_sums's
    (bb, R, 1) pair) that `ref.fixed_point_out` turns into the output.
    The contiguous and paged kernels both end here; paging only changes
    how the panels were addressed."""
    rows, t = qb.shape[1], kt.shape[2]
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, t), 2)
    qp = qpos + q_off + \
        jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1) // group
    valid = kpos < lens                                        # (bb, 1, T)
    if causal:
        valid &= kpos <= qp
    if window > 0:
        valid &= kpos > qp - window
    w = ref.softmax_weights(packed_scores(qb, kt), valid, hd)  # (bb, R, T)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, WORD, 1), 1)
    bits = jnp.concatenate([(_i32(vt[:, i:i + 1, :]) >> shifts) & 1
                            for i in range(vt.shape[1])], axis=1)[:, :hd]
    sp = ref.exact_bits_dot(w, bits, (((2,), (2,)), ((0,), (0,))))
    return sp, ref.limb_sums(w)


def _kernel(len_ref, qpos_ref, q_ref, k_ref, v_ref, o_ref, l_ref, *,
            hd: int, group: int, window: int, causal: bool):
    """`bb` batch rows of one (kv head, query block): q_ref
    (bb, 1, R, hdw) uint32, k_ref/v_ref (bb, 1, hdw, T) uint32,
    len_ref/qpos_ref (bb, 1, 1) int32, o_ref (2, bb, 1, R, hd) and l_ref
    (2, bb, 1, R, 1) int32 (high and low limbs)."""
    _store(o_ref, l_ref, _attend(
        q_ref[:, 0], k_ref[:, 0], v_ref[:, 0], len_ref[...], qpos_ref[...],
        pl.program_id(2) * (q_ref.shape[2] // group),
        hd=hd, group=group, window=window, causal=causal))


def _store(o_ref, l_ref, sums) -> None:
    for ref_, pair in zip((o_ref, l_ref), sums):
        for limb, x in enumerate(pair):
            ref_[limb, :, 0] = x


def _paged_kernel(pt_ref, len_ref, qpos_ref, q_ref, kp_hbm, vp_hbm, o_ref,
                  l_ref, kt_ref, vt_ref, sem, *, hd: int, group: int,
                  window: int, causal: bool):
    """Paged twin of `_kernel`: kp_hbm/vp_hbm are the whole page pools
    (Hkv, P, hdw, ps), left in HBM; pt_ref the flat (B * NP,) page tables
    in SMEM. Each row's pages are DMA'd into (bb, hdw, NP * ps) VMEM
    panels — only the pages the rows address, so VMEM holds no pool — then
    the shared core runs on the same panel the contiguous kernel reads.
    Sentinel entries (== P, unallocated) clip to the last pool page; those
    rows sit at positions >= kv_len and the length mask drops them."""
    bb, _, n_pos = kt_ref.shape
    p_pool, ps = kp_hbm.shape[1], kp_hbm.shape[3]
    n_pages = n_pos // ps
    head = pl.program_id(1)
    copies = []
    for r in range(bb):
        base = (pl.program_id(0) * bb + r) * n_pages
        for i in range(n_pages):
            page = jnp.minimum(pt_ref[base + i], p_pool - 1)
            for pool, panel in ((kp_hbm, kt_ref), (vp_hbm, vt_ref)):
                copies.append(pltpu.make_async_copy(
                    pool.at[head, page], panel.at[r, :, pl.ds(i * ps, ps)],
                    sem))
                copies[-1].start()
    for copy in copies:
        copy.wait()
    _store(o_ref, l_ref, _attend(
        q_ref[:, 0], kt_ref[...], vt_ref[...], len_ref[...], qpos_ref[...],
        pl.program_id(2) * (q_ref.shape[2] // group),
        hd=hd, group=group, window=window, causal=causal))


def _rows(x, b: int) -> Array:
    """Scalar or (B,) int -> (B, 1, 1) int32 (one value per batch row)."""
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1),
                            (b,)).reshape(b, 1, 1)


def packed_attention(q: Array, k: Array, v: Array, v_scale: Array,
                     kv_len: Array, q_pos: Array, *, window: int,
                     causal: bool, block_q: int, block_b: int,
                     interpret: bool | None,
                     page_table: Array | None = None) -> Array:
    """The Pallas kernel behind every packed attention entry point.

    q: (B, S, Hq, hd) float; k, v: (B, T, Hkv, hdw) uint32 contiguous
    caches, or (P, ps, Hkv, hdw) page pools when `page_table` (B, NP)
    int32 is given; v_scale: (B, Hkv); kv_len, q_pos: scalar or (B,).
    Returns (B, S, Hq, hd) in q.dtype."""
    assert k.shape[1] * (page_table.shape[1] if page_table is not None
                         else 1) < 1 << 16, "fixed-point sums need T < 2^16"
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, s, hq, hd = q.shape
    hkv, hdw = k.shape[2], k.shape[3]
    g = hq // hkv
    geo = attn_geometry(b, s, block_b, block_q, group=g,
                        aligned=not interpret)
    bb, rows = geo.bb, geo.bq * g
    if geo.ps:
        q = jnp.pad(q, ((0, 0), (0, geo.ps), (0, 0), (0, 0)))
    s_pad = s + geo.ps
    # (B, S, Hq, hd) -> (B, Hkv, S*G, hdw): row s*G + g is head kv*G + g
    qb = pack_bits(q.reshape(b, s_pad, hkv, g, hd).transpose(0, 2, 1, 3, 4)
                   ).reshape(b, hkv, s_pad * g, hdw)
    lens, qpos = _rows(kv_len, b), _rows(q_pos, b)
    if geo.pb:
        qb = jnp.pad(qb, ((0, geo.pb),) + ((0, 0),) * 3)
        # pad rows get kv_len 1 / q_pos 0 — finite math, sliced off below
        lens = jnp.pad(lens, ((0, geo.pb), (0, 0), (0, 0)),
                       constant_values=1)
        qpos = jnp.pad(qpos, ((0, geo.pb), (0, 0), (0, 0)))
    small = pl.BlockSpec((bb, 1, 1), lambda i, j, kk, *_: (i, 0, 0))
    q_spec = pl.BlockSpec((bb, 1, rows, hdw),
                          lambda i, j, kk, *_: (i, j, kk, 0))
    out_spec = [pl.BlockSpec((2, bb, 1, rows, n),
                             lambda i, j, kk, *_: (0, i, j, kk, 0))
                for n in (hd, 1)]
    out_shape = [jax.ShapeDtypeStruct((2, b + geo.pb, hkv, s_pad * g, n),
                                      jnp.int32) for n in (hd, 1)]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"))
    body = dict(hd=hd, group=g, window=window, causal=causal)
    grid = (geo.gb, hkv, geo.gs)
    if page_table is None:
        kt = k.transpose(0, 2, 3, 1)                           # (B,Hkv,hdw,T)
        vt = v.transpose(0, 2, 3, 1)
        if geo.pb:
            row_pad = ((0, geo.pb),) + ((0, 0),) * 3
            kt, vt = jnp.pad(kt, row_pad), jnp.pad(vt, row_pad)
        panel = pl.BlockSpec((bb, 1, hdw, kt.shape[3]),
                             lambda i, j, kk: (i, j, 0, 0))
        sp, l = pl.pallas_call(
            functools.partial(_kernel, **body), grid=grid,
            in_specs=[small, small, q_spec, panel, panel],
            out_specs=out_spec, out_shape=out_shape,
            compiler_params=params, interpret=interpret,
        )(lens, qpos, qb, kt, vt)
    else:
        p_pool, ps = k.shape[0], k.shape[1]
        n_pages = page_table.shape[1]
        if not interpret and ps % LANE:
            raise ValueError(f"page_size {ps}: a page is a lane slice of "
                             f"the VMEM panel, so on the TPU it must be a "
                             f"multiple of {LANE}")
        kp = k.transpose(2, 0, 3, 1)                           # (Hkv,P,hdw,ps)
        vp = v.transpose(2, 0, 3, 1)
        pt = jnp.asarray(page_table, jnp.int32)
        if geo.pb:
            # pad rows: all-sentinel tables, clipped behind the length mask
            pt = jnp.pad(pt, ((0, geo.pb), (0, 0)), constant_values=p_pool)
        pool = pl.BlockSpec(memory_space=pl.ANY)
        sp, l = pl.pallas_call(
            functools.partial(_paged_kernel, **body),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=[small, small, q_spec, pool, pool],
                out_specs=out_spec,
                scratch_shapes=[pltpu.VMEM((bb, hdw, n_pages * ps),
                                           jnp.uint32)] * 2
                + [pltpu.SemaphoreType.DMA(())]),
            out_shape=out_shape, compiler_params=params,
            interpret=interpret,
        )(pt.reshape(-1), lens, qpos, qb, kp, vp)
    out = ref.fixed_point_out(
        sp[:, :b].reshape(2, b, hkv, s_pad, g, hd),
        l[:, :b].reshape(2, b, hkv, s_pad, g, 1),
        v_scale.astype(jnp.float32)[:, :, None, None, None])
    out = out.transpose(0, 2, 1, 3, 4).reshape(b, s_pad, hq, hd)
    return out[:, :s].astype(q.dtype)


def prefill_attention_packed(q: Array, k_packed: Array, v_packed: Array,
                             v_scale: Array, kv_len: Array, q_pos: Array, *,
                             window: int = 0, causal: bool = True,
                             block_q: int | None = None,
                             block_b: int | None = None,
                             route: str | None = None,
                             interpret: bool | None = None) -> Array:
    """Chunked-prefill attention against a bit-resident KV cache.

    q: (B, S, Hq, hd) float query chunk (sign-packed here — one pack per
    chunk); k_packed, v_packed: (B, T_max, Hkv, ceil(hd/32)) uint32
    wire-format sign bitplanes (pad bits 1); v_scale: (B, Hkv) float
    per-head V magnitude; kv_len: scalar or (B,) valid cache positions —
    the chunk's own rows are already written; q_pos: scalar or (B,)
    global position of q[:, 0]. Masks positions >= kv_len, the causal
    triangle t > q_pos + i (when `causal`), and, when window > 0,
    positions <= q_pos + i - window. Query rows are processed in
    `block_q`-row sub-chunks and batch rows in `block_b`-row tiles (both
    padded up; pad rows are discarded). Returns (B, S, Hq, hd) in
    q.dtype, equal to ref.prefill_attention_packed_ref up to f32 rounding
    of the softmax and V sums (the scores are exact).

    route=None consults the tuning cache ('pallas' with tuned
    block_q/block_b, or 'xla'); an explicit route bypasses it.
    """
    b, t, hkv, hdw = k_packed.shape
    s = q.shape[1]
    hd = q.shape[-1]
    g = q.shape[2] // hkv
    if route is None:
        from repro.kernels import tune
        route, params = tune.get_route("prefill_attention", b=b, s=s, t=t,
                                       hkv=hkv, g=g, hd=hd)
        if block_q is None:
            block_q = params.get("block_q")
        if block_b is None:
            block_b = params.get("block_b")
    if route == "xla":
        return ref.prefill_attention_packed_ref(q, k_packed, v_packed,
                                                v_scale, kv_len, q_pos,
                                                window=window, causal=causal)
    if route != "pallas":
        raise ValueError(f"unknown prefill_attention route: {route}")
    return packed_attention(q, k_packed, v_packed, v_scale, kv_len, q_pos,
                            window=window, causal=causal,
                            block_q=block_q or 8, block_b=block_b or 1,
                            interpret=interpret)


def prefill_attention_packed_paged(q: Array, k_pool: Array, v_pool: Array,
                                   v_scale: Array, page_table: Array,
                                   kv_len: Array, q_pos: Array, *,
                                   window: int = 0, causal: bool = True,
                                   block_q: int | None = None,
                                   block_b: int | None = None,
                                   route: str | None = None,
                                   interpret: bool | None = None) -> Array:
    """Chunked-prefill attention against a *paged* bit-resident cache.

    q: (B, S, Hq, hd) float query chunk; k_pool, v_pool: (P, ps, Hkv,
    ceil(hd/32)) uint32 page pools; page_table: (B, NP) int32 (entries
    == P are the unallocated sentinel); v_scale: (B, Hkv); kv_len /
    q_pos: scalar or (B,) as in the contiguous entry point. Returns
    (B, S, Hq, hd) in q.dtype, equal to
    ref.prefill_attention_packed_paged_ref up to f32 rounding — and
    bit-exact with the contiguous `prefill_attention_packed` whenever
    NP*ps equals its T (shared `_attend` core; paging is pure addressing).
    """
    p_pool, ps, hkv, hdw = k_pool.shape
    b, np_ = page_table.shape
    s = q.shape[1]
    hd = q.shape[-1]
    g = q.shape[2] // hkv
    if route is None:
        from repro.kernels import tune
        route, params = tune.get_route("prefill_attention_paged", b=b, s=s,
                                       t=np_ * ps, ps=ps, p=p_pool,
                                       hkv=hkv, g=g, hd=hd)
        if block_q is None:
            block_q = params.get("block_q")
        if block_b is None:
            block_b = params.get("block_b")
    if route == "xla":
        return ref.prefill_attention_packed_paged_ref(
            q, k_pool, v_pool, v_scale, page_table, kv_len, q_pos,
            window=window, causal=causal)
    if route != "pallas":
        raise ValueError(f"unknown prefill_attention_paged route: {route}")
    return packed_attention(q, k_pool, v_pool, v_scale, kv_len, q_pos,
                            window=window, causal=causal,
                            block_q=block_q or 8, block_b=block_b or 1,
                            interpret=interpret, page_table=page_table)
