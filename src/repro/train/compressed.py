"""EF-SignSGD data-parallel train step via shard_map.

The paper's binarization thesis applied to the gradient all-reduce:
each data shard computes local grads, transmits sign(g + e) (int8 on the
wire; 1 bit packed) + one fp32 scale per tensor, keeps the residual e
locally. The reduction is a psum of signs — 32x (packed) / 4x (int8) less
DP traffic than fp32 grads, with error feedback preserving convergence
(tests/test_compressed.py shows parity with the uncompressed step).

Params are replicated across 'data' here (pure DP; the FSDP axis of the
big LM configs would compose by compressing the reduce-scatter instead —
same numerics, recorded as future work in EXPERIMENTS.md).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.api import Model
from repro.optim.base import Optimizer, apply_updates
from repro.optim.ef_signsgd import (
    EFState, compress_leaf, ef_signsgd_decompress, init_ef,
)
from repro.train.step import clip_binary_weights


def make_compressed_train_step(model: Model, opt: Optimizer, mesh,
                               axis: str = "data") -> Callable:
    """Returns step(params, opt_state, ef_state, batch) ->
    (params, opt_state, ef_state, metrics). Batch is sharded over `axis`;
    params/optimizer/EF state are per-device (EF residuals are local BY
    DESIGN — they never synchronize)."""
    cfg = model.cfg
    n_shards = mesh.shape[axis]

    def local_step(params, opt_state, ef_err, batch):
        # ef_err leaves arrive as (1, ...) — this shard's residual slice
        local_err = jax.tree.map(lambda e: e[0], ef_err)

        def loss_fn(p):
            return model.loss(p, batch, key=None)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)

        # compress only the big (>=2D) DENSE-gradient tensors — the layer
        # projections, which carry ~all the DP bytes. Embedding/LM-head
        # grads are token-sparse: sign-quantizing them turns near-zero
        # rows into dense +-scale noise (measured: training stalls), so
        # they stay fp. Biases/norm scales stay fp too (tiny).
        def one(path, g, e):
            keys = {str(getattr(k, "key", "")) for k in path}
            sparse = keys & {"embed", "lm_head"}
            if g.ndim >= 2 and not sparse:
                sign, scale, new_e = compress_leaf(g, e)
                sign_sum = jax.lax.psum(sign.astype(jnp.int32), axis)
                scale_mean = jax.lax.pmean(scale, axis)
                ghat = scale_mean * sign_sum.astype(jnp.float32) / n_shards
                return ghat, new_e
            return jax.lax.pmean(g.astype(jnp.float32), axis), e

        pairs = jax.tree_util.tree_map_with_path(one, grads, local_err)
        is_t = lambda t: isinstance(t, tuple)
        ghat = jax.tree.map(lambda t: t[0], pairs, is_leaf=is_t)
        errors = jax.tree.map(lambda t: t[1], pairs, is_leaf=is_t)
        updates, opt_state = opt.update(ghat, opt_state, params)
        params = apply_updates(params, updates)
        if cfg.quant != "none":
            params = clip_binary_weights(params)
        loss = jax.lax.pmean(loss, axis)
        new_err = jax.tree.map(lambda e: e[None], errors)  # back to (1,...)
        return params, opt_state, new_err, {"loss": loss}

    rep = P()  # replicated leaves

    def specs_like(tree, spec):
        return jax.tree.map(lambda _: spec, tree,
                            is_leaf=lambda x: hasattr(x, "shape")
                            or isinstance(x, jax.ShapeDtypeStruct))

    @functools.partial(jax.jit)
    def step(params, opt_state, ef_err, batch):
        sm = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(specs_like(params, rep), specs_like(opt_state, rep),
                      specs_like(ef_err, P(axis)),
                      specs_like(batch, P(axis))),
            out_specs=(specs_like(params, rep), specs_like(opt_state, rep),
                       specs_like(ef_err, P(axis)), {"loss": rep}),
            check_vma=False)
        return sm(params, opt_state, ef_err, batch)

    return step


def init_ef_sharded(params, n_shards: int):
    """Per-shard EF residuals: leaves (n_shards, *param.shape) fp32."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_shards,) + p.shape, jnp.float32), params)
