"""Mamba selective-scan Pallas TPU kernel.

Computes, per channel d and state n:
    h[t] = da[t] * h[t-1] + dbx[t]
    y[t] = sum_n h[t, n] * c[t, n]

This is the hardware-aware scan of Mamba [arXiv:2312.00752] re-blocked for
TPU: the (B, S, Di, N) discretized coefficients never materialize in HBM at
full sequence length per block — the grid streams (ts x blk x N) tiles
through VMEM with the recurrent state h (blk x N, fp32) resident in scratch
across sequential time steps.  Channel blocks are independent ("parallel");
the time axis is "arbitrary" (sequential).

Grid: (B, Di/blk, S/ts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(da_ref, dbx_ref, c_ref, y_ref, h_scr, *, ts):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    da = da_ref[0].astype(jnp.float32)       # (ts, blk, N)
    dbx = dbx_ref[0].astype(jnp.float32)     # (ts, blk, N)
    c = c_ref[0].astype(jnp.float32)         # (ts, N)

    def step(t, h):
        h = da[t] * h + dbx[t]               # (blk, N)
        y_ref[0, t] = jnp.sum(h * c[t][None, :], axis=-1).astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, ts, step, h_scr[...])


def mamba_scan_kernel(da, dbx, c, *, block_d=128, time_chunk=128,
                      interpret=False):
    """da, dbx: (B, S, Di, N); c: (B, S, N) -> y (B, S, Di).

    S must be a multiple of ``time_chunk`` and Di of ``block_d`` (the ops
    wrapper pads; padded channels are sliced off, padded time steps carry
    da=0/dbx=0 so the state is simply re-zeroed past the end).
    """
    B, S, Di, N = da.shape
    block_d = min(block_d, Di)
    time_chunk = min(time_chunk, S)
    assert S % time_chunk == 0 and Di % block_d == 0
    grid = (B, Di // block_d, S // time_chunk)
    kernel = functools.partial(_scan_kernel, ts=time_chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, time_chunk, block_d, N),
                         lambda b, d, t: (b, t, d, 0)),
            pl.BlockSpec((1, time_chunk, block_d, N),
                         lambda b, d, t: (b, t, d, 0)),
            pl.BlockSpec((1, time_chunk, N), lambda b, d, t: (b, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, time_chunk, block_d),
                               lambda b, d, t: (b, t, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, Di), da.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(da, dbx, c)
