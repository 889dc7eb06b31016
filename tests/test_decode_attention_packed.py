"""Bit-resident decode attention: the Pallas kernel must be bit-exact vs
the jnp oracle (ragged per-slot lengths, sliding window, GQA, odd
head_dim padded tails), and a frozen kv_bits=1 engine must decode every
smoke family end-to-end through the scheduler with per-token outputs
identical to the packed-cache oracle path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.smoke import smoke_config
from repro.core.bitpack import pack_bits, packed_width
from repro.kernels import ref
from repro.kernels.decode_attention import (
    decode_attention_packed, v_cache_scale,
)
from repro.models import ssm_lm
from repro.models import transformer as T
from repro.models.api import get_model
from repro.models.attention import decode_attention
from repro.serving.engine import Request, ServingEngine

DECODE_ARCHS = ["qwen2-72b", "musicgen-large", "llama-3.2-vision-11b",
                "falcon-mamba-7b", "recurrentgemma-2b", "dbrx-132b"]


def _case(seed, b, t, hq, hkv, hd):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, 1, hq, hd))
    kf = jax.random.normal(ks[1], (b, t, hkv, hd))
    vf = jax.random.normal(ks[2], (b, t, hkv, hd))
    return q, kf, vf, pack_bits(kf), pack_bits(vf), v_cache_scale(vf), ks[3]


# ---------------------------------------------------------------------------
# Kernel level (interpret mode): bit-exact vs the jnp oracle
# ---------------------------------------------------------------------------
@pytest.mark.kernels
@pytest.mark.parametrize("b,t,hq,hkv,hd,window,ragged", [
    (2, 24, 8, 2, 32, 0, True),     # GQA 4:1, word-aligned hd, ragged
    (1, 17, 4, 4, 20, 0, False),    # MHA, odd hd: padded-tail bits
    (3, 40, 8, 2, 16, 10, True),    # sliding window + ragged lengths
    (2, 33, 6, 3, 33, 7, True),     # everything odd + window + GQA
    (4, 9, 4, 1, 64, 0, False),     # MQA (hkv=1), scalar cache_len
    (8, 64, 8, 2, 128, 0, True),    # decode-slot batch, multi-word hd
])
def test_kernel_matches_oracle_bit_exact(b, t, hq, hkv, hd, window, ragged):
    q, _, _, kp, vp, vs, lk = _case(b * 31 + t + hq + hd, b, t, hq, hkv, hd)
    if ragged:
        lens = jax.random.randint(lk, (b,), 1, t + 1)
    else:
        lens = jnp.int32(max(1, t - 3))
    want = np.asarray(ref.decode_attention_packed_ref(
        q, kp, vp, vs, lens, window=window))
    got = np.asarray(decode_attention_packed(
        q, kp, vp, vs, lens, window=window))
    assert got.shape == (b, 1, hq, hd)
    np.testing.assert_array_equal(want, got)


@pytest.mark.kernels
@pytest.mark.parametrize("b,t,hq,hkv,hd,window", [
    (3, 21, 4, 2, 48, 5),      # block_b doesn't divide B
    (8, 40, 8, 2, 33, 0),      # odd hd tail bits, every block_b candidate
    (2, 17, 6, 3, 20, 3),      # GQA 2:1 + window + odd hd
])
def test_all_tuner_candidates_bit_exact(b, t, hq, hkv, hd, window):
    """Every (route, block_b) candidate the autotuner may ever pick for
    this kernel (tune.candidates) is bit-exact vs the oracle — plus
    clamped/non-dividing block_b values beyond the lattice."""
    from repro.kernels import tune
    q, _, _, kp, vp, vs, lk = _case(b * 11 + t + hd, b, t, hq, hkv, hd)
    lens = jax.random.randint(lk, (b,), 1, t + 1)
    want = np.asarray(ref.decode_attention_packed_ref(
        q, kp, vp, vs, lens, window=window))
    cands = tune.candidates(
        "decode_attention", dict(b=b, t=t, hkv=hkv, g=hq // hkv, hd=hd))
    assert {r for r, _ in cands} == {"xla", "pallas"}
    for route, params in cands:
        got = np.asarray(decode_attention_packed(
            q, kp, vp, vs, lens, window=window, route=route, **params))
        np.testing.assert_array_equal(want, got, err_msg=f"{route} {params}")
    for bb in (3, 16):         # clamp + pad paths outside the lattice
        got = np.asarray(decode_attention_packed(
            q, kp, vp, vs, lens, window=window, route="pallas", block_b=bb))
        np.testing.assert_array_equal(want, got, err_msg=f"block_b={bb}")


@pytest.mark.kernels
def test_kernel_matches_oracle_under_jit():
    """The serving path calls the kernel inside jit'd decode with traced
    (B,) lengths — same bit-exact contract there."""
    b, t, hq, hkv, hd = 3, 21, 4, 2, 48
    q, _, _, kp, vp, vs, lk = _case(99, b, t, hq, hkv, hd)
    lens = jax.random.randint(lk, (b,), 1, t + 1)
    got = np.asarray(jax.jit(
        lambda *a: decode_attention_packed(*a, window=5))(q, kp, vp, vs, lens))
    want = np.asarray(ref.decode_attention_packed_ref(
        q, kp, vp, vs, lens, window=5))
    np.testing.assert_array_equal(want, got)


@pytest.mark.kernels
def test_sign_inputs_match_float_decode_attention():
    """Semantics anchor: when K/V are already +-1 and v_scale == 1 the
    packed path computes exactly what the float path computes (sign dots
    are the true dots), so the quantized kernel degrades to nothing on
    genuinely binary caches."""
    b, t, hq, hkv, hd = 2, 19, 4, 2, 32
    q, kf, vf, _, _, _, lk = _case(7, b, t, hq, hkv, hd)
    ks, vsgn = ref.sign_pm1(kf), ref.sign_pm1(vf)
    qs = ref.sign_pm1(q)
    lens = jax.random.randint(lk, (b,), 1, t + 1)
    got = decode_attention_packed(qs, pack_bits(ks), pack_bits(vsgn),
                                  jnp.ones((b, hkv)), lens)
    want = decode_attention(qs, ks, vsgn, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.kernels
def test_masked_tail_is_ignored():
    """Garbage (even all-ones words) beyond cache_len must not leak into
    the output — the prefill T-padding and recycled slot rows are exactly
    such garbage."""
    b, t, hq, hkv, hd = 2, 16, 4, 2, 32
    q, _, _, kp, vp, vs, _ = _case(13, b, t, hq, hkv, hd)
    lens = jnp.asarray([5, 9], jnp.int32)
    base = np.asarray(decode_attention_packed(q, kp, vp, vs, lens))
    mask = np.arange(t)[None, :, None, None] >= np.asarray(lens)[:, None, None, None]
    kp2 = jnp.where(mask, jnp.uint32(0xFFFFFFFF), kp)
    vp2 = jnp.where(mask, jnp.uint32(0), vp)
    got = np.asarray(decode_attention_packed(q, kp2, vp2, vs, lens))
    np.testing.assert_array_equal(base, got)


# ---------------------------------------------------------------------------
# Serving mode: kv_bits=1 end-to-end through the scheduler
# ---------------------------------------------------------------------------
def _smoke_requests(cfg, rng):
    reqs = []
    for plen in (5, 3, 7):
        r = Request(prompt=rng.integers(0, cfg.vocab, plen, dtype=np.int32),
                    max_new_tokens=4)
        if cfg.family == "vlm":
            r.img_emb = rng.standard_normal(
                (cfg.n_img_tokens, cfg.d_vision)).astype(np.float32)
        reqs.append(r)
    return reqs


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_kv_bits_engine_matches_oracle_path(arch, monkeypatch):
    """Frozen kv_bits=1 engine, mixed-length traffic through the slot
    scheduler: per-token outputs must be identical when the Pallas kernel
    is swapped for the jnp packed-cache oracle — the kernel is a pure
    implementation detail of the quantized semantics."""
    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    reqs = _smoke_requests(cfg, np.random.default_rng(0))

    eng = ServingEngine(cfg, params, max_len=16, freeze=True, kv_bits=1,
                        slots=2)
    assert eng.cfg.kv_bits == 1 and eng.frozen
    outs = eng.generate(reqs)
    assert all(o.size == 4 for o in outs)

    monkeypatch.setattr(T, "decode_attention_packed",
                        ref.decode_attention_packed_ref)
    monkeypatch.setattr(ssm_lm, "decode_attention_packed",
                        ref.decode_attention_packed_ref)
    eng_oracle = ServingEngine(cfg, params, max_len=16, freeze=True,
                               kv_bits=1, slots=2)
    for a, b in zip(outs, eng_oracle.generate(reqs)):
        np.testing.assert_array_equal(a, b)


def test_freeze_kv_bits_switches_cache_layout():
    """freeze(kv_bits=1) on a live engine rebuilds model + cache: the
    packed cache allocates uint32 bitplanes and serving still works."""
    cfg = smoke_config("qwen2-72b")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_len=16, slots=2)
    assert eng.resident_cache_bytes()["packed"] == 0
    eng.freeze(kv_bits=1)
    cb = eng.resident_cache_bytes()
    assert cb["packed"] > 0
    reqs = _smoke_requests(cfg, np.random.default_rng(1))
    outs = eng.generate(reqs)
    assert all(o.size == 4 for o in outs)
    with pytest.raises(ValueError, match="kv_bits"):
        ServingEngine(cfg, params, max_len=16, kv_bits=3)


def test_resident_cache_bytes_shrink_at_least_16x():
    """The KV-cache accounting satellite + the paper-side claim: packed
    bitplanes (+ per-head scales) are >= 16x smaller than the float cache
    for word-aligned head dims."""
    cfg = smoke_config("qwen2-72b").scaled(head_dim=32)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng_f = ServingEngine(cfg, params, max_len=64, slots=4)
    eng_p = ServingEngine(cfg, params, max_len=64, slots=4, kv_bits=1)
    f, p = eng_f.resident_cache_bytes(), eng_p.resident_cache_bytes()
    assert f["packed"] == 0 and p["packed"] > 0
    assert f["total"] / p["total"] >= 16, (f, p)
    # and the packed K/V words are exactly 1 bit per float element
    hdw = packed_width(cfg.head_dim)
    assert p["packed"] * cfg.head_dim == f["total"] * hdw


@pytest.mark.kernels
@pytest.mark.parametrize("hd", [20, 32, 64, 128])
def test_fixed_point_softmax_weights(hd):
    """The integer softmax numerators are 2^30 * exp(score - max) to f32
    precision at every popcount gap, and masked positions weigh nothing."""
    j = jnp.arange(hd + 1, dtype=jnp.int32)[None]
    valid = jnp.ones_like(j, bool).at[0, -1].set(False)
    got = np.asarray(ref.softmax_weights(j + 3, valid, hd))[0]
    want = 2.0 ** 30 * np.exp(-2.0 * np.arange(hd + 1) / np.sqrt(hd))
    assert got[0] == 2 ** 30 and got[-1] == 0
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=1e-6, atol=1)


@pytest.mark.kernels
@pytest.mark.parametrize("t", [1, 7, 300])
def test_exact_bits_dot_is_integer_exact(t):
    """The byte-plane bf16 matmuls return the exact weighted bit sums, limb
    by limb, at the largest weights."""
    rng = np.random.default_rng(t)
    w = rng.integers(0, 2 ** 30 + 1, (3, 5, t)).astype(np.int32)
    w[..., 0] = 2 ** 30
    bits = rng.integers(0, 2, (3, 9, t)).astype(np.int32)
    hi, lo = ref.exact_bits_dot(jnp.asarray(w), jnp.asarray(bits),
                                (((2,), (2,)), ((0,), (0,))))
    want = np.einsum("brt,bdt->brd", w.astype(np.int64), bits)
    np.testing.assert_array_equal(
        np.asarray(hi).astype(np.int64) * 2 ** ref.LIMB + np.asarray(lo),
        want)
