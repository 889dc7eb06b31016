"""Plain reference of the served sparse-expert decoder (the program's
`moe` family), layer by layer, in jax.numpy.

Embedding, attention, norms, head, rounding and the control are those of
`reference/decoder.py` (loaded from its file, not copied); attention keeps
the keys j with i - window < j <= i where the configuration has a
`local_window`. The FFN of every layer is routed:

  xn     = norm(h)                                            [bf16]
  p      = softmax(f32(xn) . Wrouter)           (f32, the program's einsum)
  g, e   = the top_k of p, g renormalised over the k chosen     [f32]
  y_e    = sign(silu(sign(xn) . sign(Wg_e)) * (sign(xn) . sign(Wu_e)))
           . sign(Wd_e)                                       [bf16 each]
  h     += sum over the k chosen of y_e * g_e   (bf16 products, the sum
           taken as jnp.sum takes it, in the order top_k gives)

Weights come again from the seed: the router by `weights.normal`, each
stack of experts by `weights.packed_stack`, under the names and shapes of
`blocks/moe.py`. It follows the program's rounding points. Departures,
each of which the program's own path makes exact at the sizes the harness
runs:
- every expert is computed for every token and the k chosen are picked,
  where the program scatters tokens into per-expert buffers of a fixed
  capacity; the harness gives every expert room for every token
  (`capacity_factor` >= n_experts / top_k), so no token is dropped;
- the router's matmul runs at JAX's default precision on both sides, so
  on a TPU both round its operands alike; a near-tie between the k-th and
  the next expert can still go the other way where the rows are summed
  in another order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import files
from chipbench import weights as W

D = files.load_module("reference", "decoder")
B = files.load_module("blocks", "moe")


def _layer_weights(root, m: dict, layer):
    lw = {name: D._unpack(W.packed_bits(root, name, layer, k, n), k)
          for name, (k, n) in W.attn_shapes(m).items()}
    for name in W.norm_names(m, "ln1") + W.norm_names(m, "ln2"):
        lw[name] = W.norm_vector(root, name, layer, m["d_model"])
    lw["ffn.router"] = B.router(root, m, layer)
    for name in B.expert_shapes(m):
        lw[name] = B.experts(root, m, layer, name)       # packed words
    return lw


def _routed(xn, lw: dict, m: dict, low: bool):
    """The routed FFN's output (T, D) in bf16 for normed rows xn (T, D)."""
    rnd = functools.partial(D._rnd, low=low)
    shapes = B.expert_shapes(m)
    logits = jnp.einsum("td,de->te", xn.astype(jnp.float32),
                        lw["ffn.router"].astype(jnp.float32))
    gate, pick = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m["top_k"])
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    xs = D._sign(xn)

    def expert(e):
        w = {name.rsplit(".", 1)[1]: D._unpack(lw[name][e], k)
             for name, (k, _) in shapes.items()}
        return D._mlp(xs, w["w_up"], w["w_down"], w.get("w_gate"), m, low)

    ys = jax.lax.map(expert, jnp.arange(m["n_experts"]))     # (E, T, D)
    chosen = ys[pick, jnp.arange(xs.shape[0])[:, None]]        # (T, k, D)
    return (chosen * rnd(gate)[..., None]).sum(axis=1)


def _layer(h, positions, lw: dict, m: dict, low: bool, q_block: int):
    if m["mlp"] != "swiglu":
        raise ValueError(f"moe reference has no mlp {m['mlp']!r}")
    rnd = functools.partial(D._rnd, low=low)
    h = D._attend(h, positions, lw, m, low, q_block)
    b, s, d = h.shape
    xn = rnd(D._norm(h, lw["ln2.scale"], lw.get("ln2.bias"), m))
    y = _routed(xn.reshape(b * s, d), lw, m, low).reshape(b, s, d)
    return rnd(h + rnd(y))


def logits(m: dict, seed: int, tokens: np.ndarray, *, low: bool = False,
           q_block: int = 512) -> np.ndarray:
    """(B, S) token ids -> (B, S, vocab) f32 logits of the reference (or of
    the control, `low=True`), computed one layer at a time."""
    return D.run(m, seed, tokens, _layer_weights, _layer, low=low,
                 q_block=q_block)
