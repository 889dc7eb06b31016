"""Sparse-expert blocks, the program's `moe` family: every layer the same
attention (with the configuration's `local_window`, 0 for none) and a
routed FFN of `n_experts` experts, `top_k` of them for each token.

    attn     wq, wk, wv, wo   packed sign words (L, N, K/32)
    ffn      router           fp32 (L, D, E)
             experts          w_gate, w_up, w_down packed (L, E, N, K/32)
    ln1, ln2                  as in `dense`

Work kinds (`work_of`): `gemm` the attention projections (the Pallas
binary GEMMs, which the trace times as gemm), `expert` each token's top_k
experts (three +-1 GEMMs each, run in XLA's packed dot, so never counted
as `gemm`), `router` its fp32 GEMM, `attn` the packed attention over the
window, `head` as in `dense`. An expert call reads at least top_k
experts' weights: that lower bound is its weight bytes, so no share built
on them is overstated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import costs, files
from chipbench import weights as W

dense = files.load_module("blocks", "dense")

ROUTER_STD = 0.02        # the program's own init of the router
F32_BYTES = 4


def expert_shapes(m: dict) -> dict[str, tuple[int, int]]:
    """(K, N) of each expert's binary weights, by their path."""
    return W.ffn_shapes(m, "ffn.experts")


def router(root, m: dict, layer):
    return W.normal(root, "ffn.router", layer,
                    (m["d_model"], m["n_experts"]), ROUTER_STD)


def experts(root, m: dict, layer, name: str):
    k, n = expert_shapes(m)[name]
    return W.packed_stack(root, name, layer, m["n_experts"], k, n)


def params_fn(m: dict):
    """root key -> the program's frozen tree of a `moe` configuration."""
    from repro.core.packed import PackedWeight
    layers = jnp.arange(m["n_layers"])

    def build(root):
        flat = {**dense.packed_layers(root, m, W.attn_shapes(m)),
                **dense.norm_layers(root, m)}
        for name, (k, _) in expert_shapes(m).items():
            words = jax.vmap(lambda l: experts(root, m, l, name))(layers)
            flat[name] = PackedWeight(words, k=k, kind="dense",
                                      orig_dtype="float32")
        flat["ffn.router"] = jax.vmap(lambda l: router(root, m, l))(layers)
        return {**dense.top_level(root, m), "blocks": W.nest(flat)}
    return build


def router_work(m: dict, rows: float, calls: float) -> costs.Work:
    """Every layer's router: (rows, D) x (D, E) in fp32, the weights read
    once per call."""
    d, e = m["d_model"], m["n_experts"]
    return costs.Work(2.0 * rows * d * e, calls * d * e * F32_BYTES
                      + rows * (d * costs.ACT_BYTES + e * F32_BYTES)
                      ).scale(m["n_layers"])


def work_of(m: dict, w) -> dict[str, costs.Work]:
    dk, ck, kv = w.keys(m.get("local_window", 0))
    k = m["top_k"]
    out = {"gemm": costs.Work(), "expert": costs.Work(),
           "router": costs.Work()}
    for rows, calls in ((w.decoded, w.steps), (w.chunk_rows, w.chunks)):
        out["gemm"] += costs.block_gemms(m, rows, calls,
                                         costs.attn_gemm_shapes(m))
        out["expert"] += costs.block_gemms(m, rows * k, calls * k,
                                           costs.ffn_gemm_shapes(m))
        out["router"] += router_work(m, rows, calls)
    out["attn"] = costs.attention(m, dk, w.decoded, dk) + \
        costs.attention(m, ck, w.chunk_rows, kv)
    out["head"] = dense.head_work(m, w)
    return out


def tiny(m: dict) -> dict:
    """The dense cut, with at least 8 experts (2 x top_k where that is
    more, up to the published count), top_k as published, and capacity
    for every token at every expert, so the program drops none."""
    e = min(m["n_experts"], max(8, 2 * m["top_k"]))
    return {**dense.tiny(m), "n_experts": e,
            "capacity_factor": e / m["top_k"]}
