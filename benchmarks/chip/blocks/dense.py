"""Dense blocks: every layer the same attention and one FFN.

    attn  wq, wk, wv, wo   packed sign words (L, N, K/32)
    ffn   w_up, w_down (and w_gate for swiglu, geglu)   packed (L, N, K/32)
    ln1, ln2               norm scale (and bias for layernorm), fp32 (L, D)

A configuration names its block module by its "block" key (`dense` where
it names none). The module gives the harness the program's frozen tree
(`params_fn`), the operations and bytes of a window's work (`work_of`)
and the cut that a CPU test runs (`tiny`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import costs
from chipbench import weights as W

# widths of the CPU tests' cut; heads are kept in their ratio, at most 4
TINY = dict(n_layers=2, d_model=2048, head_dim=64, d_ff=2048, vocab=2048)


def top_level(root, m: dict) -> dict:
    """Embedding, output head and final norm, outside the layer stack."""
    top = {"embed": W.embed_table(root, m), "lm_head": W.head_matrix(root, m)}
    for name in W.norm_names(m, "final_norm"):
        top[name] = W.norm_vector(root, name, None, m["d_model"])
    return W.nest(top)


def packed_layers(root, m: dict, shapes: dict) -> dict:
    """{path: PackedWeight} of (L, N, K/32) words, one draw per layer."""
    from repro.core.packed import PackedWeight
    layers = jnp.arange(m["n_layers"])
    flat = {}
    for name, (k, n) in shapes.items():
        words = jax.vmap(lambda l: W.packed_bits(root, name, l, k, n))(layers)
        flat[name] = PackedWeight(words, k=k, kind="dense",
                                  orig_dtype="float32")
    return flat


def norm_layers(root, m: dict) -> dict:
    """{path: (L, D) fp32} of both norms of every layer."""
    layers = jnp.arange(m["n_layers"])
    return {name: jax.vmap(lambda l: W.norm_vector(root, name, l,
                                                   m["d_model"]))(layers)
            for name in W.norm_names(m, "ln1") + W.norm_names(m, "ln2")}


def params_fn(m: dict):
    """root key -> the program's frozen parameter tree: layer stacks of
    packed words, fp32 embedding, head and norms — what `freeze(init(key))`
    would hand the serving engine."""
    def build(root):
        flat = {**packed_layers(root, m, W.binary_shapes(m)),
                **norm_layers(root, m)}
        return {**top_level(root, m), "blocks": W.nest(flat)}
    return build


def head_work(m: dict, w) -> costs.Work:
    """The head of every decode step, and of each first token."""
    return costs.head(m, w.decoded, w.steps) + costs.head(m, w.firsts,
                                                          w.firsts)


def work_of(m: dict, w) -> dict[str, costs.Work]:
    """The window's GEMM, attention and head work by the cost functions."""
    dk, ck, kv = w.keys(m.get("local_window", 0))
    gemm = costs.block_gemms(m, w.decoded, w.steps) + \
        costs.block_gemms(m, w.chunk_rows, w.chunks)
    attn = costs.attention(m, dk, w.decoded, dk) + \
        costs.attention(m, ck, w.chunk_rows, kv)
    return {"gemm": gemm, "attn": attn, "head": head_work(m, w)}


def tiny(m: dict) -> dict:
    """The configuration at the CPU tests' widths."""
    group = m["n_heads"] // m["n_kv_heads"]
    return {**m, **TINY, "n_heads": 4, "n_kv_heads": 4 // min(group, 4)}
