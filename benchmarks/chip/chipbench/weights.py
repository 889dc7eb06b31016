"""Weights made from the seed, on the device, in the form they are served.

Every binary weight is a matrix of random signs. Its packed form (bit 1 is
+1, bit k of word w is row 32*w + k, one row of words per output column)
is drawn directly as random uint32 words, so nothing wider than the
served bits is ever made. The floating leaves (embedding, output head,
norm scales and biases) are drawn as normals.

Each leaf of each layer has a key of its own, `leaf_key(seed, name,
layer)`, so the program's stacked tree (one jitted call, all layers) and
the plain reference (layer by layer, after the window) read the same
values without either handing anything to the other.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32
EMBED_STD, HEAD_STD, NORM_STD = 0.02, 0.02, 0.1


def root_key(seed: int):
    """A threefry key from any whole seed up to 64 bits."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def leaf_key(root, name: str, layer=None):
    key = jax.random.fold_in(root, np.uint32(zlib.crc32(name.encode())))
    return key if layer is None else jax.random.fold_in(key, layer)


def binary_shapes(m: dict) -> dict[str, tuple[int, int]]:
    """(K, N) of every binary weight of one block, by its path."""
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    out = {"attn.wq": (d, h * hd), "attn.wk": (d, kv * hd),
           "attn.wv": (d, kv * hd), "attn.wo": (h * hd, d),
           "ffn.w_up": (d, f), "ffn.w_down": (f, d)}
    if m["mlp"] in ("swiglu", "geglu"):
        out["ffn.w_gate"] = (d, f)
    return out


def norm_names(m: dict, prefix: str) -> list[str]:
    names = [f"{prefix}.scale"]
    if m["norm"] == "layernorm":
        names.append(f"{prefix}.bias")
    return names


def packed_bits(root, name: str, layer, k: int, n: int):
    """(N, K/32) uint32 sign words of one layer's weight."""
    assert k % WORD == 0, f"{name}: K={k} is not a whole number of words"
    return jax.random.bits(leaf_key(root, name, layer), (n, k // WORD),
                           jnp.uint32)


def norm_vector(root, name: str, layer, d: int):
    return NORM_STD * jax.random.normal(leaf_key(root, name, layer), (d,),
                                        jnp.float32)


def embed_table(root, m: dict):
    return EMBED_STD * jax.random.normal(
        leaf_key(root, "embed"), (m["vocab"], m["d_model"]), jnp.float32)


def head_matrix(root, m: dict):
    return HEAD_STD * jax.random.normal(
        leaf_key(root, "lm_head"), (m["d_model"], m["vocab"]), jnp.float32)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def program_params_fn(m: dict):
    """root key -> the program's frozen parameter tree: layer stacks of
    packed words, fp32 embedding, head and norms — what `freeze(init(key))`
    would hand the serving engine."""
    from repro.core.packed import PackedWeight

    n_layers, d = m["n_layers"], m["d_model"]
    shapes = binary_shapes(m)

    def build(root):
        layers = jnp.arange(n_layers)
        flat = {}
        for name, (k, n) in shapes.items():
            words = jax.vmap(lambda l: packed_bits(root, name, l, k, n))(
                layers)
            flat[name] = PackedWeight(words, k=k, kind="dense",
                                      orig_dtype="float32")
        for name in norm_names(m, "ln1") + norm_names(m, "ln2"):
            flat[name] = jax.vmap(lambda l: norm_vector(root, name, l, d))(
                layers)
        top = {"embed": embed_table(root, m), "lm_head": head_matrix(root, m)}
        for name in norm_names(m, "final_norm"):
            top[name] = norm_vector(root, name, None, d)
        return {**_nest(top), "blocks": _nest(flat)}

    return build


def build_program_params(m: dict, seed: int):
    """The program's parameter tree, made on the default device in one
    jitted call."""
    return jax.block_until_ready(jax.jit(program_params_fn(m))(
        root_key(seed)))
