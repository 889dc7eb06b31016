"""Unified decoder-only transformer covering the dense / moe / audio / vlm
assigned architectures.

Features, all config-driven (repro.configs.base.ModelConfig):
  * GQA attention (n_kv_heads < n_heads), optional QKV bias (qwen2)
  * RoPE or sinusoidal positions, RMSNorm or LayerNorm
  * FFN: SwiGLU / GeGLU / squared-ReLU / GELU
  * MoE FFN (top-k, capacity-based dispatch) — llama4-scout, dbrx
  * Interleaved cross-attention groups (llama-3.2-vision); the vision
    frontend is a stub: forward takes precomputed patch embeddings
  * The paper's quantization (BBP / BC / STE) on every projection
  * lax.scan over stacked layer params (+ optional remat) so the HLO stays
    small at 80-95 layers
  * prefill / single-token decode with a sharded KV cache

Params are plain pytrees (dicts of jnp arrays); layer params carry a
leading L (or group) axis for scanning.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.bitpack import pack_bits, packed_width
from repro.core.layers import QuantMode, qmatmul, shared_pack
from repro.models.attention import (
    chunk_attention, chunk_attention_paged, decode_attention,
    decode_attention_packed, decode_attention_packed_paged,
    decode_attention_paged, flash_attention, prefill_attention_packed,
    prefill_attention_packed_paged, v_cache_scale,
)
from repro.launch.shardctx import (hint_attn_q, hint_ffn_hidden, hint_gathered, hint_residual)
from repro.models.common import (
    ffn, ffn_param_shapes, layer_norm, moe_ffn, moe_param_shapes, rms_norm,
    rope, sinusoidal_pos,
)

Array = jax.Array


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _norm_shapes(cfg: ModelConfig) -> dict:
    if cfg.norm == "layernorm":
        return {"scale": (cfg.d_model,), "bias": (cfg.d_model,)}
    return {"scale": (cfg.d_model,)}


def _self_attn_shapes(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = {"wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
         "wo": (h * hd, d)}
    if cfg.qkv_bias:
        s.update({"bq": (h * hd,), "bk": (kv * hd,), "bv": (kv * hd,)})
    return s


def _cross_attn_shapes(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dv = cfg.d_vision or d
    return {"wq": (d, h * hd), "wk": (dv, kv * hd), "wv": (dv, kv * hd),
            "wo": (h * hd, d), "gate": (1,)}


def _block_shapes(cfg: ModelConfig, kind: str) -> dict:
    s: dict[str, Any] = {"ln1": _norm_shapes(cfg), "ln2": _norm_shapes(cfg)}
    if kind == "self":
        s["attn"] = _self_attn_shapes(cfg)
    elif kind == "cross":
        s["attn"] = _cross_attn_shapes(cfg)
    else:
        raise ValueError(kind)
    if cfg.n_experts:
        s["ffn"] = moe_param_shapes(cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.mlp)
    else:
        s["ffn"] = ffn_param_shapes(cfg.d_model, cfg.d_ff, cfg.mlp)
    return s


def _init_from_shapes(key: Array, shapes, scale: float = 0.02,
                      prefix_axes: tuple[int, ...] = ()):
    """Initialize a pytree of arrays from a matching pytree of shape tuples."""
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    inits = []
    for k, shp in zip(keys, leaves):
        full = prefix_axes + shp
        if len(shp) >= 2:  # weight matrix
            inits.append(jax.random.normal(k, full, jnp.float32) * scale)
        else:              # bias / norm scale / gate -> zeros
            inits.append(jnp.zeros(full, jnp.float32))
    return jax.tree.unflatten(treedef, inits)


def init_transformer_params(key: Array, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 6)
    params: dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (cfg.vocab, cfg.d_model),
                                   jnp.float32) * 0.02,
        "final_norm": _init_from_shapes(keys[1], _norm_shapes(cfg)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            keys[2], (cfg.d_model, cfg.vocab), jnp.float32) * 0.02
    if cfg.family == "vlm":
        g = cfg.n_layers // cfg.xattn_group
        p_self = cfg.xattn_group - 1
        params["groups"] = {
            "cross": _init_from_shapes(keys[3], _block_shapes(cfg, "cross"),
                                       prefix_axes=(g,)),
            "self": _init_from_shapes(keys[4], _block_shapes(cfg, "self"),
                                      prefix_axes=(g, p_self)),
        }
    else:
        params["blocks"] = _init_from_shapes(
            keys[3], _block_shapes(cfg, "self"), prefix_axes=(cfg.n_layers,))
    return params


# ---------------------------------------------------------------------------
# Norm dispatch
# ---------------------------------------------------------------------------
def _norm(p: dict, x: Array, cfg: ModelConfig) -> Array:
    if cfg.norm == "layernorm":
        return layer_norm(x, 1.0 + p["scale"], p["bias"])
    return rms_norm(x, p["scale"])


# ---------------------------------------------------------------------------
# Sublayers
# ---------------------------------------------------------------------------
def _qkv(p: dict, xn: Array, cfg: ModelConfig, mode: QuantMode, train, key):
    keys = jax.random.split(key, 3) if key is not None else (None,) * 3
    b, s, _ = xn.shape
    # frozen binary serving: sign-pack the normed residual once; Q, K and V
    # all consume the same 1-bit wire words (3x less activation read traffic
    # and no per-projection re-pack)
    xs = shared_pack(xn, (p["wq"], p["wk"], p["wv"]), mode, train=train)
    q = qmatmul(xs, p["wq"], mode, train=train, key=keys[0])
    k = qmatmul(xs, p["wk"], mode, train=train, key=keys[1])
    v = qmatmul(xs, p["wv"], mode, train=train, key=keys[2])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def self_attn(p: dict, x: Array, cfg: ModelConfig, mode: QuantMode, *,
              train: bool, key, window: int = 0, pos_offset: int = 0,
              return_kv: bool = False):
    xn = hint_gathered(_norm(p["ln1"], x, cfg))
    kq, ko = jax.random.split(key) if key is not None else (None, None)
    q, k, v = _qkv(p["attn"], xn, cfg, mode, train, kq)
    if cfg.pos == "rope":
        positions = jnp.arange(x.shape[1]) + pos_offset
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = hint_attn_q(q)
    out = flash_attention(q, k, v, True, window, cfg.attn_chunk, pos_offset)
    out = out.reshape(x.shape[0], x.shape[1], cfg.n_heads * cfg.head_dim)
    out = hint_ffn_hidden(out)
    out = qmatmul(out, p["attn"]["wo"], mode, train=train, key=ko)
    y = x + hint_residual(out)
    if return_kv:
        return y, (k, v)
    return y


def cross_attn(p: dict, x: Array, img: Array, cfg: ModelConfig,
               mode: QuantMode, *, train: bool, key):
    """mllama-style gated cross-attention against precomputed image tokens."""
    xn = hint_gathered(_norm(p["ln1"], x, cfg))
    keys = jax.random.split(key, 4) if key is not None else (None,) * 4
    b, s, _ = xn.shape
    ni = img.shape[1]
    q = qmatmul(xn, p["attn"]["wq"], mode, train=train, key=keys[0])
    imgs = shared_pack(img, (p["attn"]["wk"], p["attn"]["wv"]), mode,
                       train=train)        # image tokens pack once for K+V
    k = qmatmul(imgs, p["attn"]["wk"], mode, train=train, key=keys[1])
    v = qmatmul(imgs, p["attn"]["wv"], mode, train=train, key=keys[2])
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, ni, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, ni, cfg.n_kv_heads, cfg.head_dim)
    out = flash_attention(q, k, v, False, 0, cfg.attn_chunk, 0)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = qmatmul(out, p["attn"]["wo"], mode, train=train, key=keys[3])
    gate = jnp.tanh(p["attn"]["gate"]).astype(out.dtype)
    return x + gate * out


def ffn_sublayer(p: dict, x: Array, cfg: ModelConfig, mode: QuantMode, *,
                 train: bool, key):
    xn = hint_gathered(_norm(p["ln2"], x, cfg))
    if cfg.n_experts:
        out, aux = moe_ffn(p["ffn"], xn, cfg.mlp, mode, top_k=cfg.top_k,
                           capacity_factor=cfg.capacity_factor,
                           train=train, key=key)
    else:
        out, aux = ffn(p["ffn"], xn, cfg.mlp, mode, train=train, key=key), {}
    return x + hint_residual(out), aux


def _self_block(p: dict, x: Array, cfg: ModelConfig, mode: QuantMode, *,
                train: bool, key, window: int = 0, pos_offset: int = 0,
                return_kv: bool = False):
    k1, k2 = jax.random.split(key) if key is not None else (None, None)
    res = self_attn(p, x, cfg, mode, train=train, key=k1, window=window,
                    pos_offset=pos_offset, return_kv=return_kv)
    x, kv = res if return_kv else (res, None)
    x, aux = ffn_sublayer(p, x, cfg, mode, train=train, key=k2)
    return x, kv, aux


# ---------------------------------------------------------------------------
# Full forward (training / scoring): tokens -> logits
# ---------------------------------------------------------------------------
def _embed(params: dict, cfg: ModelConfig, tokens: Array) -> Array:
    h = params["embed"][tokens].astype(cfg.activation_dtype)
    if cfg.pos == "sinusoidal":
        pe = sinusoidal_pos(jnp.arange(tokens.shape[1]), cfg.d_model)
        h = h + pe[None].astype(h.dtype)
    return h


def _head(params: dict, cfg: ModelConfig, h: Array) -> Array:
    h = _norm(params["final_norm"], h, cfg)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", h, w.astype(h.dtype))


def transformer_logits(params: dict, cfg: ModelConfig, tokens: Array, *,
                       img_emb: Array | None = None, train: bool = False,
                       key: Array | None = None) -> tuple[Array, dict]:
    mode = QuantMode(cfg.quant)
    h = _embed(params, cfg, tokens)
    window = cfg.local_window

    if cfg.family == "vlm":
        assert img_emb is not None, "vlm forward needs image embeddings"
        img = img_emb.astype(h.dtype)
        g = cfg.n_layers // cfg.xattn_group

        def group_body(carry, xs):
            h, aux_sum, idx = carry
            gp = xs
            kk = jax.random.fold_in(key, idx) if key is not None else None
            kc, ks = jax.random.split(kk) if kk is not None else (None, None)
            h = cross_attn(gp["cross"], h, img, cfg, mode, train=train, key=kc)
            h, aux = ffn_sublayer(gp["cross"], h, cfg, mode, train=train,
                                  key=ks)
            h = hint_residual(h)
            aux_sum += aux.get("lb_loss", 0.0)

            def self_body(carry2, sp):
                h2, j = carry2
                kj = jax.random.fold_in(kk, j) if kk is not None else None
                h2, _, aux2 = _self_block(sp, h2, cfg, mode, train=train,
                                          key=kj, window=window)
                return (hint_residual(h2), j + 1), aux2.get("lb_loss", 0.0)

            (h, _), auxs = jax.lax.scan(self_body, (h, 0), gp["self"])
            return (h, aux_sum + auxs.sum(), idx + 1), None

        body = group_body
        if cfg.remat and train:
            body = jax.checkpoint(group_body, prevent_cse=False)
        (h, lb, _), _ = jax.lax.scan(body, (h, jnp.float32(0), 0),
                                     params["groups"])
        aux = {"lb_loss": lb}
    else:
        def block_body(carry, bp):
            h, aux_sum, idx = carry
            kk = jax.random.fold_in(key, idx) if key is not None else None
            h, _, aux = _self_block(bp, h, cfg, mode, train=train, key=kk,
                                    window=_layer_window(cfg, idx))
            return (hint_residual(h), aux_sum + aux.get("lb_loss", 0.0),
                    idx + 1), None

        body = block_body
        if cfg.remat and train:
            body = jax.checkpoint(block_body, prevent_cse=False)
        (h, lb, _), _ = jax.lax.scan(body, (h, jnp.float32(0), 0),
                                     params["blocks"])
        aux = {"lb_loss": lb}

    return _head(params, cfg, h), aux


def _layer_window(cfg: ModelConfig, idx) -> int:
    # uniform-stack transformers: every layer same window (0 = global)
    return cfg.local_window


def transformer_loss(params: dict, cfg: ModelConfig, batch: dict, *,
                     key: Array | None = None) -> tuple[Array, dict]:
    """Next-token cross-entropy. batch: {tokens (B,S), [img_emb]}.

    Frozen packed params are rejected one level up (models.api wraps every
    family's loss in a params_frozen guard); the per-leaf packed_qmatmul
    train check backstops direct callers.
    """
    tokens = batch["tokens"]
    logits, aux = transformer_logits(params, cfg, tokens,
                                     img_emb=batch.get("img_emb"),
                                     train=True, key=key)
    logits = logits[:, :-1].astype(jnp.float32)
    targets = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold).mean()
    loss = nll + 0.01 * aux.get("lb_loss", 0.0)
    return loss, {"nll": nll, **aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode with KV cache
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               page_size: int | None = None,
               pool_pages: int | None = None) -> dict:
    """KV cache skeleton. kv_bits=0: float K/V in the activation dtype.
    kv_bits=1 (bit-resident serving): K/V are sign bitplanes — uint32 words
    packed along head_dim (`ceil(hd/32)` per position, the kernel wire
    format) — plus a per-(row, kv-head) fp32 V scale fixed at prefill.
    Packed caches are plain uint32 leaves, so `cache_batch_axes` and the
    scheduler's slot insertion work on them unchanged.

    `page_size` switches the K/V leaves to the *paged* layout: instead of
    one contiguous (batch, max_len, ...) panel per slot, K/V live in a
    pool of `pool_pages` fixed-size pages (default: exactly enough for
    every slot at max_len) shared by every layer — one logical page id
    addresses the same pool row in each layer — and a `page_table`
    (batch, ceil(max_len/page_size)) int32 leaf maps each slot's position
    ranges to pool pages (entries == pool_pages are the unallocated
    sentinel). The host-side owner of that table is serving.pager /
    serving.prefix_cache; v_scale and the vlm cross-attn xk/xv (computed
    once per request from image tokens) stay slot-resident."""
    packed = cfg.kv_bits == 1
    dt = cfg.activation_dtype
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    kvdt = jnp.uint32 if packed else dt
    w = packed_width(hd) if packed else hd
    paged = page_size is not None
    if paged:
        np_ = -(-max_len // page_size)
        pool = pool_pages if pool_pages is not None else batch * np_
    if cfg.family == "vlm":
        g = cfg.n_layers // cfg.xattn_group
        p_self = cfg.xattn_group - 1
        if paged:
            cache = {
                "k": jnp.zeros((g, p_self, pool, page_size, kv, w), kvdt),
                "v": jnp.zeros((g, p_self, pool, page_size, kv, w), kvdt),
                "page_table": jnp.full((batch, np_), pool, jnp.int32),
            }
        else:
            cache = {
                "k": jnp.zeros((g, p_self, batch, max_len, kv, w), kvdt),
                "v": jnp.zeros((g, p_self, batch, max_len, kv, w), kvdt),
            }
        # cross-attn KV is computed once from image tokens at prefill
        cache["xk"] = jnp.zeros((g, batch, cfg.n_img_tokens, kv, w), kvdt)
        cache["xv"] = jnp.zeros((g, batch, cfg.n_img_tokens, kv, w), kvdt)
        if packed:
            cache["v_scale"] = jnp.zeros((g, p_self, batch, kv), jnp.float32)
            cache["xv_scale"] = jnp.zeros((g, batch, kv), jnp.float32)
        return cache
    n = cfg.n_layers
    if paged:
        cache = {"k": jnp.zeros((n, pool, page_size, kv, w), kvdt),
                 "v": jnp.zeros((n, pool, page_size, kv, w), kvdt),
                 "page_table": jnp.full((batch, np_), pool, jnp.int32)}
    else:
        cache = {"k": jnp.zeros((n, batch, max_len, kv, w), kvdt),
                 "v": jnp.zeros((n, batch, max_len, kv, w), kvdt)}
    if packed:
        cache["v_scale"] = jnp.zeros((n, batch, kv), jnp.float32)
    return cache


def transformer_prefill(params: dict, cfg: ModelConfig, tokens: Array, *,
                        img_emb: Array | None = None, max_len: int | None = None
                        ) -> tuple[Array, dict]:
    """Run the prompt, return (last-position logits (B,V), cache).

    Works for fp32-master and frozen packed params alike: every projection
    routes through qmatmul, which dispatches PackedWeight leaves to the
    XNOR+popcount serving kernel (quantization done once at load time).
    """
    mode = QuantMode(cfg.quant)
    packed = cfg.kv_bits == 1
    b, s = tokens.shape
    max_len = max_len or s
    h = _embed(params, cfg, tokens)
    window = cfg.local_window

    def pad_t(x):  # (B,S,kv,hd|hdw) -> (B,T,kv,hd|hdw)
        return jnp.pad(x, ((0, 0), (0, max_len - s), (0, 0), (0, 0)))

    def emit_kv(k, v):
        """Cache rows a prefill emits for one layer. kv_bits=1: sign-pack
        K/V along head_dim into wire-format words (the PR-3 activation
        sign-pack, here applied to the cache) + the per-head V scale; the
        T padding rows are masked by cache_len at decode, never read."""
        if packed:
            return pad_t(pack_bits(k)), pad_t(pack_bits(v)), v_cache_scale(v)
        return pad_t(k), pad_t(v)

    if cfg.family == "vlm":
        img = img_emb.astype(h.dtype)

        def group_body(h, gp):
            # cache cross KV (frozen serving: img sign-packs once for K+V)
            ni = img.shape[1]
            imgs = shared_pack(img, (gp["cross"]["attn"]["wk"],
                                     gp["cross"]["attn"]["wv"]), mode)
            xk = qmatmul(imgs, gp["cross"]["attn"]["wk"], mode)
            xv = qmatmul(imgs, gp["cross"]["attn"]["wv"], mode)
            xk = xk.reshape(b, ni, cfg.n_kv_heads, cfg.head_dim)
            xv = xv.reshape(b, ni, cfg.n_kv_heads, cfg.head_dim)
            xkv = (pack_bits(xk), pack_bits(xv), v_cache_scale(xv)) if packed \
                else (xk, xv)
            h = cross_attn(gp["cross"], h, img, cfg, mode, train=False, key=None)
            h, _ = ffn_sublayer(gp["cross"], h, cfg, mode, train=False, key=None)

            def self_body(h2, sp):
                h2, kvp, _ = _self_block(sp, h2, cfg, mode, train=False,
                                         key=None, window=window,
                                         return_kv=True)
                return h2, emit_kv(*kvp)

            h, kvs = jax.lax.scan(self_body, h, gp["self"])
            return h, kvs + xkv

        h, stacked = jax.lax.scan(group_body, h, params["groups"])
        if packed:
            ks, vs, vss, xks, xvs, xvss = stacked
            cache = {"k": ks, "v": vs, "v_scale": vss,
                     "xk": xks, "xv": xvs, "xv_scale": xvss}
        else:
            ks, vs, xks, xvs = stacked
            cache = {"k": ks, "v": vs, "xk": xks, "xv": xvs}
    else:
        def block_body(h, bp):
            h, kvp, _ = _self_block(bp, h, cfg, mode, train=False, key=None,
                                    window=window, return_kv=True)
            return h, emit_kv(*kvp)

        h, stacked = jax.lax.scan(block_body, h, params["blocks"])
        if packed:
            cache = dict(zip(("k", "v", "v_scale"), stacked))
        else:
            cache = dict(zip(("k", "v"), stacked))

    logits = _head(params, cfg, h[:, -1:])[:, 0]
    return logits, cache


def _decode_self_block(bp, h, kc, vc, cfg, mode, pos, window, v_scale=None,
                       pt=None):
    """One-token self-attn block against cache. h: (B,1,D); pos: (B,) —
    each row writes its KV at its own position and masks from its own
    length (rows of a continuous-batching slot batch sit at different
    offsets). A row with pos < 0 is inactive: it computes garbage but
    writes NOTHING to the cache — the scheduler marks freed and
    mid-chunked-admission slots this way so interleaved decode bursts
    cannot corrupt a partially prefilled row. kv_bits=1: the new K/V row
    is sign-packed before the write and attention runs on the uint32
    bitplanes (XNOR+popcount scores, per-head `v_scale` V accumulation)
    — float K/V never touch the cache.

    `pt` (B, NP) int32 switches to the paged layout: kc/vc are page pools
    (P, ps, kv, ·), the write position pos maps through the slot's page
    table (page pt[b, pos//ps], row pos%ps — the scheduler pre-allocates
    every page a request can reach at admission, so active rows always
    hit a real page), and attention walks the table in the paged kernels.
    Inactive rows write at the pool-size sentinel and drop, exactly like
    the contiguous t_max convention. The parts run in named scopes
    (`qkv`, `kv_write`, `attention`, `ffn`) that a device trace shows."""
    b = h.shape[0]
    with jax.named_scope("qkv"):
        xn = _norm(bp["ln1"], h, cfg)
        q, k_new, v_new = _qkv(bp["attn"], xn, cfg, mode, False, None)
        if cfg.pos == "rope":
            positions = pos[:, None]                           # (B, 1)
            q = rope(q, positions, cfg.rope_theta)
            k_new = rope(k_new, positions, cfg.rope_theta)
    rows = jnp.arange(b)
    with jax.named_scope("kv_write"):
        if pt is not None:
            p_pool, ps = kc.shape[0], kc.shape[1]
            posc = jnp.maximum(pos, 0)
            pidx = jnp.clip(posc // ps, 0, pt.shape[1] - 1)
            at = (jnp.where(pos >= 0, pt[rows, pidx], p_pool), posc % ps)
        else:
            at = (rows, jnp.where(pos >= 0, pos, kc.shape[1]))
        # inactive rows write at the pool size / t_max sentinel: dropped
        if cfg.kv_bits == 1:
            kc = kc.at[at].set(pack_bits(k_new[:, 0]), mode="drop")
            vc = vc.at[at].set(pack_bits(v_new[:, 0]), mode="drop")
        else:
            kc = kc.at[at].set(k_new[:, 0].astype(kc.dtype), mode="drop")
            vc = vc.at[at].set(v_new[:, 0].astype(vc.dtype), mode="drop")
    with jax.named_scope("attention"):
        if pt is not None and cfg.kv_bits == 1:
            out = decode_attention_packed_paged(q, kc, vc, v_scale, pt,
                                                pos + 1, window=window)
        elif pt is not None:
            out = decode_attention_paged(q, kc, vc, pt, pos + 1,
                                         window=window)
        elif cfg.kv_bits == 1:
            out = decode_attention_packed(q, kc, vc, v_scale, pos + 1,
                                          window=window)
        else:
            out = decode_attention(q, kc, vc, pos + 1, window=window)
        out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim)
        h = h + qmatmul(out, bp["attn"]["wo"], mode)
    with jax.named_scope("ffn"):
        h, _ = ffn_sublayer(bp, h, cfg, mode, train=False, key=None)
    return h, kc, vc


def transformer_decode(params: dict, cfg: ModelConfig, token: Array,
                       cache: dict, pos: Array) -> tuple[Array, dict]:
    """One decode step. token: (B,) int32; pos: scalar or (B,) int32 (per-row
    write position = number of tokens already in that row's context; a
    scalar is broadcast — the static same-length batch; pos[b] < 0 marks
    row b inactive: it computes but writes nothing to the cache). Returns
    (logits (B,V), updated cache)."""
    mode = QuantMode(cfg.quant)
    packed = cfg.kv_bits == 1
    b = token.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    h = params["embed"][token[:, None]].astype(cfg.activation_dtype)
    if cfg.pos == "sinusoidal":
        pe = sinusoidal_pos(pos, cfg.d_model)                  # (B, d)
        h = h + pe[:, None].astype(h.dtype)
    window = cfg.local_window
    # paged layout: one page table shared by every layer (closure, not a
    # scanned leaf — each layer's pool row is addressed by the same ids)
    pt = cache.get("page_table")

    if cfg.family == "vlm":
        def group_body(h, xs):
            if packed:
                gp, xk, xv, xvs, kcs, vcs, vss = xs
            else:
                gp, xk, xv, kcs, vcs = xs
                xvs = vss = None
            # cross-attn from cached image KV
            xn = _norm(gp["cross"]["ln1"], h, cfg)
            q = qmatmul(xn, gp["cross"]["attn"]["wq"], mode)
            q = q.reshape(h.shape[0], 1, cfg.n_heads, cfg.head_dim)
            if packed:
                out = decode_attention_packed(q, xk, xv, xvs, xk.shape[1])
            else:
                out = decode_attention(q, xk, xv, xk.shape[1])
            out = out.reshape(h.shape[0], 1, cfg.n_heads * cfg.head_dim)
            gate = jnp.tanh(gp["cross"]["attn"]["gate"]).astype(out.dtype)
            h = h + gate * qmatmul(out, gp["cross"]["attn"]["wo"], mode)
            h, _ = ffn_sublayer(gp["cross"], h, cfg, mode, train=False, key=None)

            def self_body(h2, xs2):
                sp, kc, vc, vs = ((*xs2, None) if not packed else xs2)
                h2, kc, vc = _decode_self_block(sp, h2, kc, vc, cfg, mode,
                                                pos, window, v_scale=vs,
                                                pt=pt)
                return h2, (kc, vc)

            self_xs = (gp["self"], kcs, vcs) + ((vss,) if packed else ())
            h, (kcs, vcs) = jax.lax.scan(self_body, h, self_xs)
            return h, (kcs, vcs)

        group_xs = (params["groups"], cache["xk"], cache["xv"]) + \
            ((cache["xv_scale"],) if packed else ()) + \
            (cache["k"], cache["v"]) + \
            ((cache["v_scale"],) if packed else ())
        h, (ks, vs) = jax.lax.scan(group_body, h, group_xs)
        new_cache = dict(cache, k=ks, v=vs)
    else:
        def block_body(h, xs):
            bp, kc, vc, vs = ((*xs, None) if not packed else xs)
            h, kc, vc = _decode_self_block(bp, h, kc, vc, cfg, mode, pos,
                                           window, v_scale=vs, pt=pt)
            return h, (kc, vc)

        block_xs = (params["blocks"], cache["k"], cache["v"]) + \
            ((cache["v_scale"],) if packed else ())
        h, (ks, vs) = jax.lax.scan(block_body, h, block_xs)
        new_cache = dict(cache, k=ks, v=vs)

    with jax.named_scope("head"):
        logits = _head(params, cfg, h)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Chunked prefill: advance one slot's prompt by one fixed-shape chunk
# ---------------------------------------------------------------------------
def _chunk_self_block(bp, h, kc, vc, vs, cfg, mode, positions, widx, kv_len,
                      pos, n_valid, window, pt_row=None):
    """One self-attn block over a prefill chunk against the slot's cache
    row. h: (1, C, D); kc/vc: (1, T, kv, hd|hdw); vs: (1, kv) running
    per-head V scale (kv_bits=1) or None. The chunk's K/V rows are written
    first (pad rows i >= n_valid drop), then the chunk's queries attend to
    everything written so far — cross-chunk rows AND the intra-chunk causal
    triangle come out of the same cache panel. kv_bits=1: the write is a
    sign-pack, the V scale updates as a running mean over [0, kv_len), and
    attention is XOR+popcount over the uint32 bitplanes
    (`prefill_attention_packed`) — float K/V never touch the cache.

    `pt_row` (NP,) int32 switches to the paged layout: kc/vc are page
    pools (P, ps, kv, ·), chunk row i lands at page pt_row[positions[i]
    // ps], row positions[i] % ps (pad rows write at the pool-size
    sentinel and drop), and attention walks the table in the paged
    kernels. The running V-scale update is layout-independent and shared
    verbatim — which is what keeps paged prefill == contiguous prefill
    bit-exact. The parts run in the named scopes of `_decode_self_block`."""
    c = h.shape[1]
    with jax.named_scope("qkv"):
        xn = _norm(bp["ln1"], h, cfg)
        q, k_new, v_new = _qkv(bp["attn"], xn, cfg, mode, False, None)
        if cfg.pos == "rope":
            q = rope(q, positions, cfg.rope_theta)
            k_new = rope(k_new, positions, cfg.rope_theta)
    with jax.named_scope("kv_write"):
        if pt_row is not None:
            p_pool, ps = kc.shape[0], kc.shape[1]
            pidx = jnp.clip(positions // ps, 0, pt_row.shape[0] - 1)
            at = (jnp.where(jnp.arange(c) < n_valid, pt_row[pidx], p_pool),
                  positions % ps)
        else:
            at = (0, widx)
        if cfg.kv_bits == 1:
            kc = kc.at[at].set(pack_bits(k_new[0]), mode="drop")
            vc = vc.at[at].set(pack_bits(v_new[0]), mode="drop")
            # running mean |v| over (positions so far, head_dim): equals
            # the whole-prompt v_cache_scale once the last chunk lands
            absm = jnp.mean(jnp.abs(v_new[0].astype(jnp.float32)), axis=-1)
            msk = (jnp.arange(c) < n_valid)[:, None]
            vs = (vs * pos.astype(jnp.float32)
                  + jnp.sum(absm * msk, axis=0)[None]) / \
                kv_len.astype(jnp.float32)
        else:
            kc = kc.at[at].set(k_new[0].astype(kc.dtype), mode="drop")
            vc = vc.at[at].set(v_new[0].astype(vc.dtype), mode="drop")
    with jax.named_scope("attention"):
        if pt_row is not None and cfg.kv_bits == 1:
            out = prefill_attention_packed_paged(q, kc, vc, vs, pt_row[None],
                                                 kv_len, pos, window=window)
        elif pt_row is not None:
            out = chunk_attention_paged(q, kc, vc, pt_row[None], kv_len, pos,
                                        window=window)
        elif cfg.kv_bits == 1:
            out = prefill_attention_packed(q, kc, vc, vs, kv_len, pos,
                                           window=window)
        else:
            out = chunk_attention(q, kc, vc, kv_len, pos, window=window)
        out = out.reshape(1, c, cfg.n_heads * cfg.head_dim)
        h = h + qmatmul(out, bp["attn"]["wo"], mode)
    with jax.named_scope("ffn"):
        h, _ = ffn_sublayer(bp, h, cfg, mode, train=False, key=None)
    return h, kc, vc, vs


def transformer_prefill_chunk(params: dict, cfg: ModelConfig, tokens: Array,
                              cache: dict, slot: Array, pos: Array,
                              n_valid: Array, *, img_emb: Array | None = None
                              ) -> tuple[Array, dict]:
    """Advance one slot's prefill by one fixed-shape chunk.

    tokens: (1, C) int32, right-padded — only the first `n_valid` are real;
    cache: the scheduler's FULL shared slot cache; slot / pos / n_valid:
    traced int32 scalars (pos = tokens already written for this slot). The
    chunk's K/V rows land incrementally at positions [pos, pos+n_valid) of
    the slot's cache row, so admission compiles once per chunk shape, never
    per prompt length, and a decode burst can run between chunks. Returns
    (logits (1, V) at the chunk's last real token, updated cache) — the
    logits feed first-token sampling on the final chunk and are dead-code
    eliminated for earlier chunks. img_emb (vlm) is passed on the first
    chunk only: it computes and caches the per-group cross-attention KV;
    later chunks cross-attend to the cached (packed, when kv_bits=1) rows.
    """
    mode = QuantMode(cfg.quant)
    packed = cfg.kv_bits == 1
    _, c = tokens.shape
    window = cfg.local_window
    slot = jnp.asarray(slot, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    idx = jnp.arange(c, dtype=jnp.int32)
    positions = idx + pos
    kv_len = pos + n_valid
    h = params["embed"][tokens].astype(cfg.activation_dtype)
    if cfg.pos == "sinusoidal":
        h = h + sinusoidal_pos(positions, cfg.d_model)[None].astype(h.dtype)

    def dslice(x, ax):
        return jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=ax)

    def dput(x, rows, ax):
        return jax.lax.dynamic_update_slice_in_dim(x, rows.astype(x.dtype),
                                                   slot, axis=ax)

    # paged layout: the K/V pools are shared by every slot, so they scan
    # through whole (never dsliced per slot) and the slot's page-table row
    # directs the writes; vlm xk/xv and the V scales stay slot-resident
    paged = "page_table" in cache
    pt_row = dslice(cache["page_table"], 0)[0] if paged else None  # (NP,)

    if cfg.family == "vlm":
        if paged:
            widx = None
            kcs_all, vcs_all = cache["k"], cache["v"]
        else:
            t_max = cache["k"].shape[3]
            widx = jnp.where(idx < n_valid, positions, t_max)
            kcs_all, vcs_all = dslice(cache["k"], 2), dslice(cache["v"], 2)
        xk_all, xv_all = dslice(cache["xk"], 1), dslice(cache["xv"], 1)
        group_xs = (params["groups"], kcs_all, vcs_all) + \
            ((dslice(cache["v_scale"], 2),) if packed else ()) + \
            (xk_all, xv_all) + \
            ((dslice(cache["xv_scale"], 1),) if packed else ())

        def group_body(h, xs):
            if packed:
                gp, kcs, vcs, vss, xk, xv, xvs = xs
            else:
                gp, kcs, vcs, xk, xv = xs
                xvs = None
            ca = gp["cross"]["attn"]
            if img_emb is not None:     # first chunk: compute + cache xKV
                img = img_emb.astype(h.dtype)
                ni = img.shape[1]
                imgs = shared_pack(img, (ca["wk"], ca["wv"]), mode)
                xkf = qmatmul(imgs, ca["wk"], mode).reshape(
                    1, ni, cfg.n_kv_heads, cfg.head_dim)
                xvf = qmatmul(imgs, ca["wv"], mode).reshape(
                    1, ni, cfg.n_kv_heads, cfg.head_dim)
                if packed:
                    xk, xv, xvs = (pack_bits(xkf), pack_bits(xvf),
                                   v_cache_scale(xvf))
                else:
                    xk, xv = xkf.astype(xk.dtype), xvf.astype(xv.dtype)
            # cross-attn from the cached image KV (decode-style)
            xn = _norm(gp["cross"]["ln1"], h, cfg)
            q = qmatmul(xn, ca["wq"], mode).reshape(
                1, c, cfg.n_heads, cfg.head_dim)
            if packed:
                out = prefill_attention_packed(q, xk, xv, xvs, xk.shape[1],
                                               0, causal=False)
            else:
                out = chunk_attention(q, xk, xv, xk.shape[1], 0, causal=False)
            out = out.reshape(1, c, cfg.n_heads * cfg.head_dim)
            gate = jnp.tanh(ca["gate"]).astype(out.dtype)
            h = h + gate * qmatmul(out, ca["wo"], mode)
            h, _ = ffn_sublayer(gp["cross"], h, cfg, mode, train=False,
                                key=None)

            def self_body(h2, xs2):
                sp, kc, vc, vs = ((*xs2, None) if not packed else xs2)
                h2, kc, vc, vs = _chunk_self_block(
                    sp, h2, kc, vc, vs, cfg, mode, positions, widx, kv_len,
                    pos, n_valid, window, pt_row=pt_row)
                return h2, (kc, vc) + ((vs,) if packed else ())

            self_xs = (gp["self"], kcs, vcs) + ((vss,) if packed else ())
            h, st = jax.lax.scan(self_body, h, self_xs)
            return h, st + (xk, xv) + ((xvs,) if packed else ())

        h, ys = jax.lax.scan(group_body, h, group_xs)
        if packed:
            ks, vls, vss, xks, xvs_, xvss = ys
        else:
            ks, vls, xks, xvs_ = ys
        if paged:
            new_cache = dict(cache, k=ks, v=vls)
        else:
            new_cache = dict(cache, k=dput(cache["k"], ks, 2),
                             v=dput(cache["v"], vls, 2))
        new_cache["xk"] = dput(cache["xk"], xks, 1)
        new_cache["xv"] = dput(cache["xv"], xvs_, 1)
        if packed:
            new_cache["v_scale"] = dput(cache["v_scale"], vss, 2)
            new_cache["xv_scale"] = dput(cache["xv_scale"], xvss, 1)
    else:
        if paged:
            widx = None
            block_xs = (params["blocks"], cache["k"], cache["v"]) + \
                ((dslice(cache["v_scale"], 1),) if packed else ())
        else:
            t_max = cache["k"].shape[2]
            widx = jnp.where(idx < n_valid, positions, t_max)
            block_xs = (params["blocks"], dslice(cache["k"], 1),
                        dslice(cache["v"], 1)) + \
                ((dslice(cache["v_scale"], 1),) if packed else ())

        def block_body(h, xs):
            bp, kc, vc, vs = ((*xs, None) if not packed else xs)
            h, kc, vc, vs = _chunk_self_block(
                bp, h, kc, vc, vs, cfg, mode, positions, widx, kv_len, pos,
                n_valid, window, pt_row=pt_row)
            return h, (kc, vc) + ((vs,) if packed else ())

        h, st = jax.lax.scan(block_body, h, block_xs)
        if paged:
            new_cache = dict(cache, k=st[0], v=st[1])
        else:
            new_cache = dict(cache, k=dput(cache["k"], st[0], 1),
                             v=dput(cache["v"], st[1], 1))
        if packed:
            new_cache["v_scale"] = dput(cache["v_scale"], st[2], 1)

    hl = jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, axis=1)
    with jax.named_scope("head"):
        return _head(params, cfg, hl)[:, 0], new_cache
