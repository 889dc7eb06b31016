"""Pallas TPU kernel for the Mamba selective scan (falcon-mamba hot spot).

The recurrence h_t = exp(dt_t * A) h_{t-1} + dt_t B_t x_t ;  y_t = C_t.h_t
is memory-roofline-bound in pure JAX: either an associative scan
materializes O(log T) full (T, D, N) tree levels, or a sequential scan
round-trips the (D, N) state through HBM every step. This kernel keeps h
resident in VMEM scratch across the whole time axis — HBM traffic reduces
to the (T, D)/(T, N) inputs and (T, D) output, the true minimum.

Grid: (B, D/bd, T/bt) with the time axis "arbitrary" (sequential): the
scratch state persists across the T-blocks of one (batch, channel-block).

Used for inference/prefill (fwd only). Training uses the remat'd
sequential-chunk form in repro.models.ssm whose backward is handled by
jax AD; fusing the backward into a second Pallas kernel is the natural
next step on real hardware (DESIGN.md §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _ssm_kernel(dt_ref, xi_ref, b_ref, c_ref, a_ref, y_ref, hout_ref,
                h_scr, *, bt: int, nt: int):
    """Refs per grid step:
      dt_ref, xi_ref: (1, bt, bd); b_ref, c_ref: (1, bt, N); a_ref: (bd, N)
      y_ref: (1, bt, bd); hout_ref: (1, bd, N); h_scr: VMEM (bd, N) f32.
    """

    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a_mat = a_ref[...].astype(jnp.float32)          # (bd, N)

    def step(i, h):
        dt_t = dt_ref[0, i, :].astype(jnp.float32)  # (bd,)
        xi_t = xi_ref[0, i, :].astype(jnp.float32)
        b_t = b_ref[0, i, :].astype(jnp.float32)    # (N,)
        c_t = c_ref[0, i, :].astype(jnp.float32)
        a = jnp.exp(dt_t[:, None] * a_mat)          # (bd, N)
        h = a * h + (dt_t * xi_t)[:, None] * b_t[None, :]
        y_ref[0, i, :] = jnp.sum(h * c_t[None, :], axis=1).astype(
            y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bt, step, h_scr[...])
    h_scr[...] = h

    @pl.when(pl.program_id(2) == nt - 1)
    def _emit():
        hout_ref[0] = h.astype(hout_ref.dtype)


def selective_scan(dt: Array, xi: Array, bmat: Array, cmat: Array,
                   a_mat: Array, *, bd: int = 512, bt: int = 256,
                   interpret: bool | None = None) -> tuple[Array, Array]:
    """dt, xi: (B, T, D) — step sizes and conv'd inputs; bmat, cmat:
    (B, T, N); a_mat: (D, N) (negative-real A). Returns (y (B, T, D) f32,
    h_final (B, D, N) f32)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, t, d = dt.shape
    n = bmat.shape[-1]
    bd = min(bd, d)
    bt = min(bt, t)
    assert d % bd == 0, (d, bd)
    pad_t = (-t) % bt
    if pad_t:  # dt=0 pads are exact identities (a=1, bx=0)
        dt = jnp.pad(dt, ((0, 0), (0, pad_t), (0, 0)))
        xi = jnp.pad(xi, ((0, 0), (0, pad_t), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad_t), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad_t), (0, 0)))
    nt = (t + pad_t) // bt
    grid = (b, d // bd, nt)

    y, h_fin = pl.pallas_call(
        functools.partial(_ssm_kernel, bt=bt, nt=nt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, bt, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, bt, n), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, bt, n), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((bd, n), lambda i, j, k: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bd), lambda i, j, k: (i, k, j)),
            pl.BlockSpec((1, bd, n), lambda i, j, k: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t + pad_t, d), jnp.float32),
            jax.ShapeDtypeStruct((b, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt, xi, bmat, cmat, a_mat)
    return y[:, :t], h_fin
