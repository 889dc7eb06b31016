"""Paged packed attention: walking a (B, n_pages) page table over shared
K/V pools must be pure addressing — bit-exact vs the contiguous packed
kernels/oracles whenever the table covers the same positions, for ragged
lengths, sliding window, GQA/MQA, odd head_dim, and kv_bits=0 (the float
gather wrappers). Pool rows no table entry points at hold garbage on
purpose: the tests prove the length masks keep it out of every output."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bitpack import pack_bits
from repro.kernels import ref
from repro.kernels.decode_attention import (
    decode_attention_packed, decode_attention_packed_paged, v_cache_scale,
)
from repro.kernels.prefill_attention import (
    prefill_attention_packed, prefill_attention_packed_paged,
)
from repro.models.attention import (
    chunk_attention, chunk_attention_paged, decode_attention,
    decode_attention_paged,
)


def _paginate(rng, contiguous, ps, extra_pages=3):
    """Scatter a (B, T, ...) contiguous cache into a shuffled page pool:
    returns (pool, page_table) with pool rows beyond the table filled
    with garbage of the same dtype."""
    b, t = contiguous.shape[:2]
    assert t % ps == 0
    np_ = t // ps
    p_pool = b * np_ + extra_pages
    perm = rng.permutation(p_pool)[:b * np_].reshape(b, np_)
    tail = contiguous.shape[2:]
    arr = np.asarray(contiguous)
    if arr.dtype == np.uint32:
        pool = rng.integers(0, 2**32, (p_pool, ps) + tail, dtype=np.uint32)
    else:
        pool = rng.standard_normal((p_pool, ps) + tail).astype(arr.dtype)
    pool[perm.reshape(-1)] = arr.reshape(b * np_, ps, *tail)
    return jnp.asarray(pool), jnp.asarray(perm, jnp.int32)


def _case(seed, b, t, hq, hkv, hd):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, 1, hq, hd))
    kf = jax.random.normal(ks[1], (b, t, hkv, hd))
    vf = jax.random.normal(ks[2], (b, t, hkv, hd))
    return q, kf, vf, ks[3]


# ---------------------------------------------------------------------------
# Decode: paged oracle == contiguous oracle == paged Pallas kernel
# ---------------------------------------------------------------------------
@pytest.mark.kernels
@pytest.mark.parametrize("b,t,ps,hq,hkv,hd,window,ragged", [
    (2, 24, 4, 8, 2, 32, 0, True),    # GQA 4:1, word-aligned hd
    (1, 16, 8, 4, 4, 20, 0, False),   # MHA, odd hd padded-tail bits
    (3, 40, 5, 8, 2, 16, 10, True),   # sliding window + ragged
    (2, 36, 6, 6, 3, 33, 7, True),    # odd everything + window + GQA
    (4, 8, 8, 4, 1, 64, 0, False),    # MQA, single page per slot
    (8, 64, 16, 8, 2, 128, 0, True),  # slot batch, multi-word hd
])
def test_paged_decode_bit_exact(b, t, ps, hq, hkv, hd, window, ragged):
    rng = np.random.default_rng(b * 31 + t)
    q, kf, vf, lk = _case(b * 31 + t + hd, b, t, hq, hkv, hd)
    kp, vp, vs = pack_bits(kf), pack_bits(vf), v_cache_scale(vf)
    lens = (jax.random.randint(lk, (b,), 1, t + 1) if ragged
            else jnp.int32(max(1, t - 3)))
    k_pool, pt = _paginate(rng, kp, ps)
    v_pool, _ = _paginate(np.random.default_rng(rng.integers(1 << 30)),
                          vp, ps)
    # v pages must mirror k pages: re-scatter with the same table
    v_pool = jnp.asarray(np.asarray(v_pool))
    v_pool = v_pool.at[pt.reshape(-1)].set(
        jnp.asarray(vp).reshape(b * (t // ps), ps, hkv, vp.shape[-1]))

    want = np.asarray(ref.decode_attention_packed_ref(
        q, kp, vp, vs, lens, window=window))
    got_ref = np.asarray(ref.decode_attention_packed_paged_ref(
        q, k_pool, v_pool, vs, pt, lens, window=window))
    np.testing.assert_array_equal(want, got_ref)

    for bb in (1, 2, 4):
        if bb > b:
            continue
        got = np.asarray(decode_attention_packed_paged(
            q, k_pool, v_pool, vs, pt, lens, window=window,
            route="pallas", block_b=bb, interpret=True))
        np.testing.assert_array_equal(want, got)


@pytest.mark.kernels
def test_paged_decode_sentinel_rows_are_inert():
    """Entries past a slot's allocation hold the sentinel (== pool size):
    truncating the table there must not change the output as long as
    cache_len stays within the allocated prefix."""
    b, t, ps, hq, hkv, hd = 3, 32, 4, 4, 2, 32
    rng = np.random.default_rng(9)
    q, kf, vf, _ = _case(5, b, t, hq, hkv, hd)
    kp, vp, vs = pack_bits(kf), pack_bits(vf), v_cache_scale(vf)
    k_pool, pt = _paginate(rng, kp, ps)
    v_pool = jnp.asarray(np.asarray(_paginate(rng, vp, ps)[0]))
    v_pool = v_pool.at[pt.reshape(-1)].set(
        jnp.asarray(vp).reshape(-1, ps, hkv, vp.shape[-1]))
    lens = jnp.asarray([5, 12, 9], jnp.int32)   # within 3 pages each
    p_pool = k_pool.shape[0]
    cut = pt.at[:, 3:].set(p_pool)              # drop pages past position 12
    for route in ("xla", "pallas"):
        full = np.asarray(decode_attention_packed_paged(
            q, k_pool, v_pool, vs, pt, lens, route=route, interpret=True))
        trunc = np.asarray(decode_attention_packed_paged(
            q, k_pool, v_pool, vs, cut, lens, route=route, interpret=True))
        np.testing.assert_array_equal(full, trunc)


@pytest.mark.kernels
def test_paged_float_decode_matches_contiguous():
    b, t, ps, hq, hkv, hd = 2, 24, 8, 4, 2, 32
    rng = np.random.default_rng(3)
    q, kf, vf, lk = _case(11, b, t, hq, hkv, hd)
    lens = jax.random.randint(lk, (b,), 1, t + 1)
    k_pool, pt = _paginate(rng, kf, ps)
    v_pool = jnp.asarray(np.asarray(_paginate(rng, vf, ps)[0]))
    v_pool = v_pool.at[pt.reshape(-1)].set(
        jnp.asarray(vf).reshape(-1, ps, hkv, hd))
    want = np.asarray(decode_attention(q, kf, vf, lens, window=5))
    got = np.asarray(decode_attention_paged(q, k_pool, v_pool, pt, lens,
                                            window=5))
    np.testing.assert_array_equal(want, got)


# ---------------------------------------------------------------------------
# Prefill (chunked cross-attention over the already-written cache)
# ---------------------------------------------------------------------------
@pytest.mark.kernels
@pytest.mark.parametrize("b,s,t,ps,hq,hkv,hd,window", [
    (2, 4, 24, 4, 8, 2, 32, 0),
    (1, 8, 16, 8, 4, 4, 20, 0),
    (3, 4, 40, 5, 8, 2, 16, 10),
    (2, 6, 36, 6, 6, 3, 33, 7),
])
def test_paged_prefill_bit_exact(b, s, t, ps, hq, hkv, hd, window):
    rng = np.random.default_rng(b + s + t)
    key = jax.random.PRNGKey(b * 7 + t)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, s, hq, hd))
    kf = jax.random.normal(ks[1], (b, t, hkv, hd))
    vf = jax.random.normal(ks[2], (b, t, hkv, hd))
    kp, vp, vs = pack_bits(kf), pack_bits(vf), v_cache_scale(vf)
    kv_len = jax.random.randint(ks[3], (b,), s, t + 1)
    q_pos = kv_len - s                      # chunk sits at the cache tail
    k_pool, pt = _paginate(rng, kp, ps)
    v_pool = jnp.asarray(np.asarray(_paginate(rng, vp, ps)[0]))
    v_pool = v_pool.at[pt.reshape(-1)].set(
        jnp.asarray(vp).reshape(-1, ps, hkv, vp.shape[-1]))

    want = np.asarray(prefill_attention_packed(
        q, kp, vp, vs, kv_len, q_pos, window=window, route="xla"))
    got_ref = np.asarray(ref.prefill_attention_packed_paged_ref(
        q, k_pool, v_pool, vs, pt, kv_len, q_pos, window=window))
    np.testing.assert_array_equal(want, got_ref)

    for bq, bb in ((1, 1), (2, 2), (4, 1)):
        if bb > b or bq > s:
            continue
        got = np.asarray(prefill_attention_packed_paged(
            q, k_pool, v_pool, vs, pt, kv_len, q_pos, window=window,
            route="pallas", block_q=bq, block_b=bb, interpret=True))
        np.testing.assert_array_equal(want, got)


@pytest.mark.kernels
def test_paged_float_chunk_matches_contiguous():
    b, s, t, ps, hq, hkv, hd = 2, 4, 24, 4, 4, 2, 32
    rng = np.random.default_rng(8)
    key = jax.random.PRNGKey(21)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, s, hq, hd))
    kf = jax.random.normal(ks[1], (b, t, hkv, hd))
    vf = jax.random.normal(ks[2], (b, t, hkv, hd))
    kv_len = jnp.asarray([9, 17], jnp.int32)
    q_pos = kv_len - s
    k_pool, pt = _paginate(rng, kf, ps)
    v_pool = jnp.asarray(np.asarray(_paginate(rng, vf, ps)[0]))
    v_pool = v_pool.at[pt.reshape(-1)].set(
        jnp.asarray(vf).reshape(-1, ps, hkv, hd))
    want = np.asarray(chunk_attention(q, kf, vf, kv_len, q_pos))
    got = np.asarray(chunk_attention_paged(q, k_pool, v_pool, pt,
                                           kv_len, q_pos))
    np.testing.assert_array_equal(want, got)


@pytest.mark.kernels
def test_tpu_path_rejects_unaligned_page_size():
    """Compiled for the chip, a page is a lane slice of the kernel's VMEM
    panel: a page size that is not a multiple of 128 is refused up front
    with the reason, not deep inside the TPU compiler."""
    b, t, ps, hkv, hd = 2, 16, 8, 2, 32
    q = jnp.zeros((b, 1, hkv, hd))
    pool = jnp.zeros((b * t // ps, ps, hkv, 1), jnp.uint32)
    pt = jnp.arange(b * t // ps, dtype=jnp.int32).reshape(b, t // ps)
    with pytest.raises(ValueError, match="multiple of 128"):
        decode_attention_packed_paged(q, pool, pool, jnp.ones((b, hkv)), pt,
                                      jnp.full((b,), t), route="pallas",
                                      interpret=False)
