"""Fused RMSNorm (+ optional residual-add) Pallas TPU kernel.

One HBM round-trip instead of three (add, mean-square, scale): a (block_rows
x D) tile is normalized entirely in VMEM.  Grid: (rows/block,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _rmsnorm_residual_kernel(x_ref, r_ref, w_ref, o_ref, res_ref, *, eps):
    x = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    res_ref[...] = x.astype(res_ref.dtype)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps) * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_kernel(x, w, residual=None, *, eps=1e-6, block_rows=256,
                   interpret=False):
    """x: (N, D), w: (D,); residual: optional (N, D) added before the norm.
    Returns y, or (y, x+residual) when residual is given."""
    N, D = x.shape
    block_rows = min(block_rows, N)
    pad = -N % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        if residual is not None:
            residual = jnp.pad(residual, ((0, pad), (0, 0)))
    grid = ((N + pad) // block_rows,)
    row_spec = pl.BlockSpec((block_rows, D), lambda i: (i, 0))
    w_spec = pl.BlockSpec((D,), lambda i: (0,))
    if residual is None:
        out = pl.pallas_call(
            functools.partial(_rmsnorm_kernel, eps=eps),
            grid=grid, in_specs=[row_spec, w_spec], out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            interpret=interpret)(x, w)
        return out[:N]
    out, res = pl.pallas_call(
        functools.partial(_rmsnorm_residual_kernel, eps=eps),
        grid=grid, in_specs=[row_spec, row_spec, w_spec],
        out_specs=(row_spec, row_spec),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, x.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret)(x, residual, w)
    return out[:N], res[:N]
