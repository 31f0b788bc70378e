"""RG-LRU gated linear recurrence Pallas TPU kernel (Griffin
[arXiv:2402.19427]): h[t] = a[t] * h[t-1] + b[t], elementwise over the
recurrent width.  Width blocks are parallel; time is sequential with the
state vector resident in VMEM scratch.

Grid: (B, W/blk, S/ts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h_ref, h_scr, *, ts):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[0].astype(jnp.float32)          # (ts, blk)
    b = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + b[t]
        h_ref[0, t] = h.astype(h_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, ts, step, h_scr[...])


def rglru_scan_kernel(a, b, *, block_w=128, time_chunk=256, interpret=False):
    """a, b: (B, S, W) -> h (B, S, W); S % time_chunk == 0, W % block_w == 0."""
    B, S, W = a.shape
    block_w = min(block_w, W)
    time_chunk = min(time_chunk, S)
    assert S % time_chunk == 0 and W % block_w == 0
    grid = (B, W // block_w, S // time_chunk)
    kernel = functools.partial(_rglru_kernel, ts=time_chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, time_chunk, block_w), lambda b_, w, t: (b_, t, w)),
            pl.BlockSpec((1, time_chunk, block_w), lambda b_, w, t: (b_, t, w)),
        ],
        out_specs=pl.BlockSpec((1, time_chunk, block_w),
                               lambda b_, w, t: (b_, t, w)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
