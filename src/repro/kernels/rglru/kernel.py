"""Pallas TPU kernel for the RG-LRU gated linear recurrence.

Grid: (B, C // bc, T // ct) with time innermost; the (1, bc) hidden-state
carry lives in VMEM scratch, persisting across time chunks and re-zeroed
whenever a new (batch, channel-block) row starts. Channels are the lane
dimension (bc a multiple of 128); the fori_loop body is a pure VPU
elementwise multiply-add, so the kernel is memory-bound by design — its
purpose is fusing the scan so HBM sees each element exactly once instead
of the O(T) small-kernel launches an unfused scan lowers to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rglru_kernel(a_ref, b_ref, h_ref, carry_ref, *, ct: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    def step(i, h):
        h = a_ref[0, i] * h + b_ref[0, i]
        h_ref[0, i] = h.astype(h_ref.dtype)
        return h

    carry_ref[0] = jax.lax.fori_loop(0, ct, step, carry_ref[0])


def rglru_pallas(a, b, *, bc: int = 128, ct: int = 128,
                 interpret: bool = True):
    """a, b: (B, T, C) -> h: (B, T, C) fp32."""
    bsz, t, ch = a.shape
    assert t % ct == 0 and ch % bc == 0
    grid = (bsz, ch // bc, t // ct)
    blk = pl.BlockSpec((1, ct, bc), lambda bi, ci, ti: (bi, ti, ci))
    return pl.pallas_call(
        functools.partial(_rglru_kernel, ct=ct),
        grid=grid,
        in_specs=[blk, blk],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((bsz, t, ch), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
