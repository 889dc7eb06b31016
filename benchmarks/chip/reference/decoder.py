"""Plain reference of the served decoder, layer by layer, in jax.numpy.

It imports nothing of the program and reads nothing the program made: the
weights come again from the seed (`chipbench.weights`), and every step is
written out here from the model's definition as the configuration file
states it:

  h      = embed[token] (+ sinusoidal position)                 [bf16]
  block  xn = norm(h); q, k, v = sign(xn) . sign(Wq|Wk|Wv)      [bf16]
         (RoPE on q and k where the configuration says rope)
         score(i, j) = sign(q_i) . sign(k_j), j <= i (and j > i - window
                       where the configuration has a `local_window`)
         p(i, j)     = softmax(score / sqrt(hd)) held in 30-bit fixed
                       point: floor(2^30 * exp((score - max) / sqrt(hd)))
                       with exp built by a fixed chain of f32 products
         a_i   = sign(sum_j p(i, j) sign(v_j))    (the per-head V scale is
                 positive and only the sign reaches the next GEMM)
         h    += sign(a) . sign(Wo)
         xn    = norm(h); gelu:   f = gelu(sign(xn) . sign(Wup))
                          swiglu: f = silu(sign(xn) . sign(Wg)) * (sign(xn) . sign(Wup))
         h    += sign(f) . sign(Wdown)
  logits = norm(h) . Whead                  (f32 result, bf16 operands)

with sign(0) = +1. Every +-1 product is an exact integer on the MXU (bf16
operands, f32 sums), and every value the configuration keeps in bf16 is
rounded to bf16 where it is made. `rnd` is that rounding; the control
(`low=True`) rounds through float8_e5m2 first, the nearest precision
below the configuration's bfloat16.

`run` drives any layer stack from the seed: `reference/moe_decoder.py`
gives it its own layer and weights and reuses the parts here.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

BF16 = jnp.bfloat16
FP8 = jnp.float8_e5m2
HIGHEST = jax.lax.Precision.HIGHEST


def _rnd(x, low: bool):
    x = x.astype(BF16) if not low else x.astype(FP8).astype(BF16)
    return x


def _sign(x):
    """+-1 in bf16, sign(0) = +1."""
    return jnp.where(x >= 0, 1.0, -1.0).astype(BF16)


def _pm1_dot(a, b):
    """Exact integer products of +-1 operands, as f32."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _unpack(words, k: int):
    """(N, K/32) uint32 words -> (K, N) +-1 bf16 (bit 1 is +1)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts) & 1
    return (bits.reshape(words.shape[0], k).T.astype(BF16) * 2 - 1)


def _norm(h, scale, bias, m: dict):
    x = h.astype(jnp.float32)
    if m["norm"] == "layernorm":
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + m["layernorm_eps"]) * \
            (1.0 + scale) + bias
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + m["rms_norm_eps"]) * (1.0 + scale)


def _rope(x, positions, theta: float):
    """x (B, S, H, hd) bf16; rotate halves by position * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _sinusoid(positions, d: int):
    half = d // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _rungs(hd: int) -> list[float]:
    return [math.exp(-(2 ** k) * 2.0 / math.sqrt(hd))
            for k in range(hd.bit_length())]


def _attn_signs(q, k, v, hd: int, q_block: int, window: int = 0):
    """Sign of sum_j p(i, j) sign(v_j) per (row, query, head, dim), exact,
    over keys j <= i (and j > i - window for a window above 0).
    q (B, S, Hq, hd), k, v (B, S, Hkv, hd) as +-1 bf16."""
    b, s, hq, _ = q.shape
    g = hq // k.shape[2]
    kh = jnp.repeat(k, g, axis=2).transpose(0, 2, 3, 1)      # (B,H,hd,S)
    vh = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3)      # (B,H,S,hd)
    rungs = [jnp.float32(r) for r in _rungs(hd)]
    outs = []
    for q0 in range(0, s, q_block):
        qb = q[:, q0:q0 + q_block].transpose(0, 2, 1, 3)     # (B,H,Q,hd)
        score = _pm1_dot(qb, kh).astype(jnp.int32)           # (B,H,Q,S)
        pc = (hd - score) // 2
        qi = q0 + jnp.arange(qb.shape[2])[:, None]
        valid = jnp.arange(s)[None, :] <= qi
        if window:
            valid &= jnp.arange(s)[None, :] > qi - window
        pc = jnp.where(valid, pc, hd + 1)
        j = pc - jnp.min(pc, axis=-1, keepdims=True)
        e = jnp.ones(j.shape, jnp.float32)
        for bit, rung in enumerate(rungs):
            e = jnp.where(((j >> bit) & 1) != 0, e * rung, e)
        w = jnp.where(valid, (e * jnp.float32(2 ** 30)).astype(jnp.int32), 0)
        # exact sum_j w_j sign(v_j): 8-bit digits of w through bf16, each
        # digit's sum below 2^24, then carries from the low digit up
        digits = [_pm1_dot(((w >> (8 * i)) & 255).astype(BF16), vh)
                  .astype(jnp.int32) for i in range(4)]
        for i in range(3):
            carry = digits[i] >> 8                           # floor division
            digits[i + 1] = digits[i + 1] + carry
        outs.append(_sign(digits[3]).transpose(0, 2, 1, 3))  # (B,Q,H,hd)
    return jnp.concatenate(outs, axis=1)


def _attend(h, positions, lw: dict, m: dict, low: bool, q_block: int):
    """h after the block's attention and its residual add."""
    rnd = functools.partial(_rnd, low=low)
    b, s, d = h.shape
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    xs = _sign(rnd(_norm(h, lw["ln1.scale"], lw.get("ln1.bias"), m)))
    q = rnd(_pm1_dot(xs, lw["attn.wq"])).reshape(b, s, hq, hd)
    k = rnd(_pm1_dot(xs, lw["attn.wk"])).reshape(b, s, hkv, hd)
    v = rnd(_pm1_dot(xs, lw["attn.wv"])).reshape(b, s, hkv, hd)
    if m["pos"] == "rope":
        q = rnd(_rope(q, positions, m["rope_theta"]))
        k = rnd(_rope(k, positions, m["rope_theta"]))
    a = _attn_signs(_sign(q), _sign(k), _sign(v), hd, q_block,
                    m.get("local_window", 0))
    return rnd(h + rnd(_pm1_dot(a.reshape(b, s, hq * hd), lw["attn.wo"])))


def _mlp(xs, w_up, w_down, w_gate, m: dict, low: bool):
    """One FFN's output in bf16 from the signs xs of its input."""
    rnd = functools.partial(_rnd, low=low)
    up = rnd(_pm1_dot(xs, w_up))
    if m["mlp"] == "gelu":
        f = rnd(jax.nn.gelu(up))
    elif m["mlp"] == "swiglu":
        gate = rnd(_pm1_dot(xs, w_gate))
        f = rnd(rnd(jax.nn.silu(gate)) * up)
    else:
        raise ValueError(f"reference has no mlp {m['mlp']!r}")
    return rnd(_pm1_dot(_sign(f), w_down))


def _layer(h, positions, lw: dict, m: dict, low: bool, q_block: int):
    rnd = functools.partial(_rnd, low=low)
    h = _attend(h, positions, lw, m, low, q_block)
    xs = _sign(rnd(_norm(h, lw["ln2.scale"], lw.get("ln2.bias"), m)))
    return rnd(h + _mlp(xs, lw["ffn.w_up"], lw["ffn.w_down"],
                        lw.get("ffn.w_gate"), m, low))


def _layer_weights(root, m: dict, layer):
    lw = {name: _unpack(W.packed_bits(root, name, layer, k, n), k)
          for name, (k, n) in W.binary_shapes(m).items()}
    for name in W.norm_names(m, "ln1") + W.norm_names(m, "ln2"):
        lw[name] = W.norm_vector(root, name, layer, m["d_model"])
    return lw


def logits(m: dict, seed: int, tokens: np.ndarray, *, low: bool = False,
           q_block: int = 512) -> np.ndarray:
    """(B, S) token ids -> (B, S, vocab) f32 logits of the reference (or of
    the control, `low=True`), computed one layer at a time."""
    return run(m, seed, tokens, _layer_weights, _layer, low=low,
               q_block=q_block)


def run(m: dict, seed: int, tokens: np.ndarray, layer_weights, layer, *,
        low: bool, q_block: int) -> np.ndarray:
    """Logits of a stack whose every layer is `layer(h, positions,
    layer_weights(root, m, index), m, low, q_block)`."""
    root = W.root_key(seed)
    tok = jnp.asarray(tokens, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(tok.shape[1], dtype=jnp.int32),
                                 tok.shape)

    @jax.jit
    def embed(tok, positions):
        h = _rnd(W.embed_table(root, m)[tok], low)
        if m["pos"] == "sinusoidal":
            h = _rnd(h + _rnd(_sinusoid(positions, m["d_model"]), low), low)
        return h

    @functools.partial(jax.jit, donate_argnums=(0,))
    def block(h, positions, index):
        return layer(h, positions, layer_weights(root, m, index), m, low,
                     q_block)

    @jax.jit
    def head(h):
        fn = {n.split(".")[1]: W.norm_vector(root, n, None, m["d_model"])
              for n in W.norm_names(m, "final_norm")}
        x = _rnd(_norm(h, fn["scale"], fn.get("bias"), m), low)
        wh = _rnd(W.head_matrix(root, m), low)
        return jnp.matmul(x.astype(jnp.float32), wh.astype(jnp.float32),
                          precision=HIGHEST)

    h = embed(tok, positions)
    for index in range(m["n_layers"]):
        h = block(h, positions, jnp.int32(index))
    return np.asarray(jax.device_get(head(h)))
