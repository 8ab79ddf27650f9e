"""Pallas TPU kernel: BlockSpec-tiled GEMM with the paper's mapping knobs.

This is the MXU rendering of CarbonPATH's workload-mapping vocabulary
(Sec IV-A, Algorithm 1). The systolic array of the paper is the TPU MXU;
the (t_M, t_K, t_N) tile sizes are the BlockSpec block shapes; and the
three dataflows map to grid iteration orders:

  OS  (output stationary) — grid (m, n, k), k innermost. Partial sums stay
      in a VMEM scratch accumulator and each output block is written once:
      the paper's reason OS minimizes data movement, rendered literally.
  WS  (weight stationary)  — grid (n, k, m), m innermost. The weight block
      is resident across the m sweep; output partial sums spill to a
      per-k-slab HBM buffer and are reduced by the wrapper — the psum
      write-back traffic the paper charges WS for.
  IS  (input stationary)   — grid (m, k, n), n innermost. Symmetric to WS
      with the input block resident.

split-K adds a leading slab axis for OS: each K-shard accumulates into its
own output slab, and the wrapper performs the destination reduction
(paper: partial sums shipped over D2D to the destination chiplet; here:
the slab-sum the distributed layer lowers to a reduce-scatter).

Block shapes should be multiples of 128 in the lane dimension and of 8
(fp32) / 16 (bf16) in the sublane dimension so the MXU tiles align.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _os_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    """Output-stationary: accumulate over the innermost k axis in VMEM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _os_splitk_kernel(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    """Output-stationary with a leading split-K slab axis: grid
    (s, m, n, k); each slab holds the partial sum of its K shard."""
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[0] += jnp.dot(a_ref[0], b_ref[0],
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _spill_kernel(a_ref, b_ref, o_ref):
    """WS/IS: one partial product per (k-slab, m, n) block; the stationary
    operand is pinned by its index_map across the innermost sweep."""
    o_ref[0] = jnp.dot(a_ref[0], b_ref[0],
                       preferred_element_type=jnp.float32)


def os_gemm(a, b, *, bm, bk, bn, out_dtype, interpret):
    m, k = a.shape
    _, n = b.shape
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_os_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)


def os_gemm_splitk(a, b, *, splits, bm, bk, bn, out_dtype, interpret):
    """Returns (splits, m, n) partial slabs; caller reduces over axis 0."""
    m, k = a.shape
    _, n = b.shape
    k_shard = k // splits
    grid = (splits, m // bm, n // bn, k_shard // bk)
    nk = grid[3]
    return pl.pallas_call(
        functools.partial(_os_splitk_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk),
                         lambda s, i, j, kk, nk=nk: (0, i, s * nk + kk)),
            pl.BlockSpec((1, bk, bn),
                         lambda s, i, j, kk, nk=nk: (0, s * nk + kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda s, i, j, kk: (s, i, j)),
        out_shape=jax.ShapeDtypeStruct((splits, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((1, bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(a[None], b[None])


def ws_gemm_partials(a, b, *, bm, bk, bn, interpret):
    """Weight-stationary: grid (n, k, m), m innermost; psum slabs out."""
    m, k = a.shape
    _, n = b.shape
    grid = (n // bn, k // bk, m // bm)
    return pl.pallas_call(
        _spill_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda j, kk, i: (0, i, kk)),
            # weight block: index ignores the innermost m axis -> resident
            pl.BlockSpec((1, bk, bn), lambda j, kk, i: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda j, kk, i: (kk, i, j)),
        out_shape=jax.ShapeDtypeStruct((k // bk, m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(a[None], b[None])


def is_gemm_partials(a, b, *, bm, bk, bn, interpret):
    """Input-stationary: grid (m, k, n), n innermost; psum slabs out."""
    m, k = a.shape
    _, n = b.shape
    grid = (m // bm, k // bk, n // bn)
    return pl.pallas_call(
        _spill_kernel,
        grid=grid,
        in_specs=[
            # input block: index ignores the innermost n axis -> resident
            pl.BlockSpec((1, bm, bk), lambda i, kk, j: (0, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda i, kk, j: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda i, kk, j: (kk, i, j)),
        out_shape=jax.ShapeDtypeStruct((k // bk, m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(a[None], b[None])
