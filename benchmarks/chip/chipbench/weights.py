"""Weights made from the seed, on the device, in the form they are served.

Every binary weight is a matrix of random signs. Its packed form (bit 1 is
+1, bit k of word w is row 32*w + k, one row of words per output column)
is drawn directly as random uint32 words, so nothing wider than the
served bits is ever made. The floating leaves (embedding, output head,
norm scales and biases) are drawn as normals.

Each leaf of each layer has a key of its own, `leaf_key(seed, name,
layer)`, so the program's stacked tree (one jitted call, all layers) and
the plain reference (layer by layer, after the window) read the same
values without either handing anything to the other. Every draw is one of
`packed_bits`, `packed_stack` or `normal`: a block module
(`blocks/<name>.py`), which assembles the program's tree, and its
reference call the same function with the same name, layer and shape.
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


def attn_shapes(m: dict) -> dict[str, tuple[int, int]]:
    """(K, N) of every binary attention weight of one block, by its path."""
    d, h, kv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return {"attn.wq": (d, h * hd), "attn.wk": (d, kv * hd),
            "attn.wv": (d, kv * hd), "attn.wo": (h * hd, d)}


def ffn_shapes(m: dict, prefix: str = "ffn") -> dict[str, tuple[int, int]]:
    """(K, N) of every binary weight of one FFN (or of each expert, with
    prefix `ffn.experts`), by its path."""
    d, f = m["d_model"], m["d_ff"]
    out = {f"{prefix}.w_up": (d, f), f"{prefix}.w_down": (f, d)}
    if m["mlp"] in ("swiglu", "geglu"):
        out[f"{prefix}.w_gate"] = (d, f)
    return out


def binary_shapes(m: dict) -> dict[str, tuple[int, int]]:
    """(K, N) of every binary weight of one dense block, by its path."""
    return {**attn_shapes(m), **ffn_shapes(m)}


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


def packed_stack(root, name: str, layer, e: int, k: int, n: int):
    """(E, N, K/32) uint32 sign words of one layer's E experts, from one
    key: expert i's words are row i of the draw."""
    assert k % WORD == 0, f"{name}: K={k} is not a whole number of words"
    return jax.random.bits(leaf_key(root, name, layer), (e, n, k // WORD),
                           jnp.uint32)


def normal(root, name: str, layer, shape: tuple[int, ...], std: float):
    """A float32 leaf of N(0, std^2) values."""
    return std * jax.random.normal(leaf_key(root, name, layer), shape,
                                   jnp.float32)


def norm_vector(root, name: str, layer, d: int):
    return normal(root, name, layer, (d,), NORM_STD)


def embed_table(root, m: dict):
    return normal(root, "embed", None, (m["vocab"], m["d_model"]), EMBED_STD)


def head_matrix(root, m: dict):
    return normal(root, "lm_head", None, (m["d_model"], m["vocab"]),
                  HEAD_STD)


def nest(flat: dict) -> dict:
    """{"a.b": leaf} -> {"a": {"b": leaf}}."""
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split(".")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def build_program_params(params_fn, seed: int):
    """The program's parameter tree, `params_fn(root key)` of the
    configuration's block module, made on the default device in one
    jitted call."""
    return jax.block_until_ready(jax.jit(params_fn)(root_key(seed)))
