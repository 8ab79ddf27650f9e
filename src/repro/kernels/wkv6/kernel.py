"""Pallas TPU kernel for the RWKV-6 WKV recurrence.

TPU adaptation: the recurrence is sequential in T but embarrassingly
parallel over G = batch x heads, so the grid is (G, T // ct) with the time
axis innermost ("arbitrary" semantics). The (D, D) state matrix lives in a
VMEM scratch that persists across time chunks and is re-initialized when a
new G row begins. Inside a chunk, a fori_loop performs ct rank-1 updates;
all operands for the chunk are VMEM-resident blocks of (1, ct, D).

VMEM budget per program: 4 x (ct x D) operand blocks + (D, D) state +
(ct, D) output, fp32. For D = 64, ct = 256 that's ~0.4 MB — comfortably
under the ~16 MB/core VMEM of current TPUs; BlockSpecs keep every matmul
dimension a multiple of the 8x128 register tile when D >= 128 (smaller D
still works; Pallas pads lanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref, *, ct: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0]                                  # (D,)

    def step(i, s):
        r_t = r_ref[0, i]                         # (D,)
        k_t = k_ref[0, i]
        v_t = v_ref[0, i]
        w_t = w_ref[0, i]
        kv = k_t[:, None] * v_t[None, :]          # (Dk, Dv)
        y = jnp.sum(r_t[:, None] * (s + u[:, None] * kv), axis=0)
        y_ref[0, i] = y.astype(y_ref.dtype)
        return w_t[:, None] * s + kv

    s_ref[...] = jax.lax.fori_loop(0, ct, step, s_ref[...])


def wkv6_pallas(r, k, v, w, u, *, ct: int = 128, interpret: bool = True):
    """r/k/v/w: (G, T, D); u: (G, D). Returns y: (G, T, D) in fp32."""
    g, t, d = r.shape
    assert t % ct == 0, f"T={t} not divisible by chunk {ct}"
    grid = (g, t // ct)
    blk = pl.BlockSpec((1, ct, d), lambda gi, c: (gi, c, 0))
    return pl.pallas_call(
        functools.partial(_wkv6_kernel, ct=ct),
        grid=grid,
        in_specs=[blk, blk, blk, blk,
                  pl.BlockSpec((1, d), lambda gi, c: (gi, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((g, t, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
