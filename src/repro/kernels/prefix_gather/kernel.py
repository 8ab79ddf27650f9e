"""Pallas kernel: prefix-table gather + per-chiplet-slot split-K select.

The hottest inner loop of the device evaluator's stage 3
(:mod:`repro.pathfinding.device`): every system gathers, per chiplet
slot, the difference of two entries of a per-(array, sram, dataflow)
prefix-sum table — Algorithm 1 assigns contiguous tile ranges, so a
core's ScaleSim aggregate is ``pref[row, end] - pref[row, start]`` — for
the five sim metrics, from the split-K table the system's mapping picks.

Packed layout (:func:`pack_tables`). The int64 prefix values reach
1.5e10 (WL2), past int32 and float32's exact range, and the TPU vector
unit has no int64. Each value ``v`` is stored as the exact int32 pair
``hi = v >> 31``, ``lo = v & (2**31 - 1)``. One *group* holds one
(split, row, tile-boundary) entry: 16 int32 lanes, the F hi words then
the F lo words. Groups of both split tables are laid end to end
(``g = off[split] + row * (T_split + 1) + boundary``) and folded into a
lane-dense ``[G / 8, 128]`` int32 array, 8 groups per 128-lane row. The
caller turns each slot's clipped ``[start, end]`` range into two group
indices, so the kernel does no clipping or split logic of its own.

Kernel. The packed table is one VMEM-resident block (a constant index
map: copied in once). The group indices ride in SMEM blocks of ``B``
systems. For each slot and each sub-tile of 8 systems, every system's
two groups are fetched as whole 128-lane rows (a dynamic *sublane*
offset, never a dynamic lane), the other 7 groups of each row are
masked to zero, and the rows are staged into two ``[8, 128]`` scratch
tiles. ``end - start`` of the two tiles, summed over the row's 8 group
positions with three static lane rotations, leaves the slot's exact
hi/lo differences in every group; the slot's group position is kept.
After all slots the ``[8, 128]`` tile (slot ``c`` in lanes
``16c .. 16c + 15``) is stored whole. int32 arithmetic wraps, so the
differences are exact even where a partial sum overflows; the caller
recombines ``dhi * 2**31 + dlo`` in int64, bit-equal to the int64
reference gather.

Layer-blocked table (:func:`select_groups_grouped`). A network's
distinct GEMMs each pack to a block of the same ``[rows, 128]`` shape,
stacked ``[B, rows, 128]``; a whole stack can outgrow VMEM. The systems
then come in groups, each gathering from one block: group ``g``'s
systems fill whole grid steps of up to 128, and a scalar-prefetched
block id per group picks the table block its steps copy in.
Consecutive steps of one block copy it once. The kernel body is the
same.

On CPU the same kernel runs in interpreter mode (tests); on TPU it is
compiled, and interpret mode is never used there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
GROUP = 16                  # lanes per packed group
GROUPS_PER_ROW = LANES // GROUP
LO_BITS = 31
BLOCK = 128                 # systems per grid step
VMEM_LIMIT = 64 * 1024 * 1024


def pack_tables(pref0, pref1):
    """Pack two split-K prefix stacks into the kernel's int32 layout.

    ``pref0``/``pref1`` are ``[F, R, L0]`` / ``[F, R, L1]`` non-negative
    integer prefix tables (``L = T + 1``; the tile axes may differ).
    Returns ``(table [rows, 128] int32, (R, L0, L1))``; the group of
    ``(split, r, t)`` is ``split * R * L0 + r * L_split + t``."""
    p0 = np.asarray(pref0, dtype=np.int64)
    p1 = np.asarray(pref1, dtype=np.int64)
    F, R, L0 = p0.shape
    F1, R1, L1 = p1.shape
    assert (F, R) == (F1, R1), (p0.shape, p1.shape)
    assert 2 * F <= GROUP, f"{F} metrics do not fit a {GROUP}-lane group"
    assert p0.min(initial=0) >= 0 and p1.min(initial=0) >= 0
    assert max(p0.max(initial=0), p1.max(initial=0)) < 2 ** 62
    # [F, G] with G = R*L0 + R*L1 groups, in group order
    vals = np.concatenate([p0.reshape(F, -1), p1.reshape(F, -1)], axis=1)
    G = vals.shape[1]
    rows = -(-G // GROUPS_PER_ROW)
    rows = -(-rows // 8) * 8          # whole (8, 128) tiles
    packed = np.zeros((rows * GROUPS_PER_ROW, GROUP), dtype=np.int32)
    packed[:G, :F] = (vals >> LO_BITS).T
    packed[:G, F:2 * F] = (vals & ((1 << LO_BITS) - 1)).T
    return packed.reshape(rows, LANES), (R, L0, L1)


def _select_kernel(ge_ref, gs_ref, tab_ref, out_ref, e_scr, s_scr, *,
                   nc: int):
    # every constant is a typed int32: the callers trace under 64-bit
    # types, where a bare Python int would become an int64 constant that
    # Mosaic cannot lower
    i32 = np.int32
    lane_row = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // i32(GROUP)
    lane_grp = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1) // i32(GROUP)

    def fetch(g):
        # the row holding group g, with its other 7 groups zeroed
        row = tab_ref[pl.ds(g // i32(GROUPS_PER_ROW), 1), :]
        return jnp.where(lane_row == g % i32(GROUPS_PER_ROW), row, i32(0))

    def sub_tile(j):
        acc = jnp.zeros((8, LANES), jnp.int32)
        for c in range(nc):
            for b in range(8):
                k = (j * i32(8) + i32(b)) * i32(GROUPS_PER_ROW) + i32(c)
                e_scr[pl.ds(b, 1), :] = fetch(ge_ref[k])
                s_scr[pl.ds(b, 1), :] = fetch(gs_ref[k])
            d = e_scr[...] - s_scr[...]
            for shift in (GROUP, 2 * GROUP, 4 * GROUP):
                d = d + pltpu.roll(d, i32(shift), 1)
            acc = jnp.where(lane_grp == i32(c), d, acc)
        out_ref[pl.ds(pl.multiple_of(j * i32(8), 8), 8), :] = acc
        return j + i32(1)

    # a while loop: fori_loop's static trip count would become a scan
    # with an int64 counter
    n_sub = i32(out_ref.shape[0] // 8)
    jax.lax.while_loop(lambda j: j < n_sub, sub_tile, i32(0))


def select_groups(table, ge, gs, *, interpret: bool):
    """``[N, 128]`` int32 packed differences of groups ``ge - gs``.

    ``ge``/``gs`` are ``[N, C]`` group indices (``C <= 8``); row ``n``
    holds slot ``c``'s hi/lo differences in lanes ``16c ..``."""
    N, C = ge.shape
    assert C <= GROUPS_PER_ROW, C
    # 8 index slots per system and 128 systems per step: SMEM blocks of
    # 1024 words, the 1-D SMEM tiling
    block = min(BLOCK, -(-N // 8) * 8)
    n_pad = -(-N // block) * block
    pad = ((0, n_pad - N), (0, GROUPS_PER_ROW - C))
    ge = jnp.pad(ge.astype(jnp.int32), pad).reshape(-1)
    gs = jnp.pad(gs.astype(jnp.int32), pad).reshape(-1)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    zero = np.int32(0)  # typed: index maps are traced under 64-bit types
    out = pl.pallas_call(
        functools.partial(_select_kernel, nc=C),
        grid=(n_pad // block,),
        in_specs=[smem((block * GROUPS_PER_ROW,), lambda i: (i,)),
                  smem((block * GROUPS_PER_ROW,), lambda i: (i,)),
                  pl.BlockSpec(table.shape, lambda i: (zero, zero))],
        out_specs=pl.BlockSpec((block, LANES), lambda i: (i, zero)),
        out_shape=jax.ShapeDtypeStruct((n_pad, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((8, LANES), jnp.int32),
                        pltpu.VMEM((8, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(ge, gs, table)
    return out[:N]


def _grouped_kernel(gids_ref, ge_ref, gs_ref, tab_ref, out_ref, e_scr, s_scr,
                    *, nc: int):
    del gids_ref  # read by the table's index map only
    row = np.int32(0)  # typed, as in _select_kernel
    _select_kernel(ge_ref.at[row], gs_ref.at[row], tab_ref, out_ref, e_scr,
                   s_scr, nc=nc)


def select_groups_grouped(table, gids, ge, gs, *, interpret: bool):
    """``[G, N, 128]`` int32 packed differences: group ``g``'s systems
    gather ``ge[g] - gs[g]`` from table block ``gids[g]``.

    ``table`` is a layer-blocked ``[B, rows, 128]`` stack of
    :func:`pack_tables` blocks, ``gids`` ``[G]`` block ids, ``ge``/``gs``
    ``[G, N, C]`` group indices local to a block (``C <= 8``)."""
    G, N, C = ge.shape
    assert C <= GROUPS_PER_ROW, C
    # a group's systems fill whole blocks of up to 128; the index blocks
    # are [1, 8 * block] rows of a 3-D operand, whose SMEM tiling takes
    # any block that spans the whole last two dimensions
    block = min(BLOCK, -(-N // 8) * 8)
    n_pad = -(-N // block) * block
    per = n_pad // block  # grid steps per group
    pad = ((0, 0), (0, n_pad - N), (0, GROUPS_PER_ROW - C))
    rows = (G * per, 1, block * GROUPS_PER_ROW)
    ge = jnp.pad(ge.astype(jnp.int32), pad).reshape(rows)
    gs = jnp.pad(gs.astype(jnp.int32), pad).reshape(rows)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    # typed: index maps are traced under 64-bit types
    zero, per_i = np.int32(0), np.int32(per)
    index = smem((pl.squeezed,) + rows[1:], lambda i, g: (i, zero, zero))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G * per,),
        in_specs=[index, index,
                  pl.BlockSpec((pl.squeezed,) + table.shape[1:],
                               lambda i, g: (g[i // per_i], zero, zero))],
        out_specs=pl.BlockSpec((block, LANES), lambda i, g: (i, zero)),
        scratch_shapes=[pltpu.VMEM((8, LANES), jnp.int32),
                        pltpu.VMEM((8, LANES), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_grouped_kernel, nc=C),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G * n_pad, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(gids.astype(jnp.int32), ge, gs, table)
    return out.reshape(G, n_pad, LANES)[:, :N]
