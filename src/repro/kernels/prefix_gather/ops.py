"""Public wrapper for the prefix-gather kernel.

``prefix_select_gather`` turns each chiplet slot's tile range into the
two packed-table group indices (clip to the row's true tile total, pick
the split-K table), runs the kernel and recombines the exact hi/lo
differences into int64. The kernel is compiled on TPU and runs in
interpreter mode everywhere else; the choice follows the backend and
cannot be overridden, so interpret mode is never reached on a TPU.

The kernel call carries a ``jax.custom_batching.custom_vmap`` rule: the
stacked ScenarioEngine calls it from inside a ``vmap`` over scenario
cells, and the rule flattens the mapped cell axis into the kernel's
system axis (``[B, P, C] -> [B*P, C]``) instead of relying on
``pallas_call``'s own batching. The packed table stays an unbatched
operand (cells share one workload-stacked table), so one kernel launch
covers the whole grid. A Mosaic kernel cannot be partitioned by XLA, so
under a mesh set with ``jax.set_mesh`` (the ScenarioEngine's sharded
scenario axis) the call runs in a ``shard_map``: each device gathers
for its own cells' rows against a replicated table.

``prefix_select_gather_blocked`` is the same gather from one block of a
layer-blocked table (a network engine's per-GEMM stack, see
:func:`~repro.kernels.prefix_gather.kernel.select_groups_grouped`). It
is called once per layer under a ``vmap`` over the layers, itself under
the ``vmap`` over cells; its rule folds each mapped axis into the
kernel's group axis, the outer one slowest, so the whole ``[cells,
layers, P]`` population is still one launch, grouped layer by layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import custom_batching
from jax.sharding import PartitionSpec as P

from repro.kernels.prefix_gather import kernel as K


@functools.lru_cache(maxsize=None)
def _select_fn(interpret: bool):
    """The custom_vmap-wrapped kernel call for one interpret setting."""
    kernel = functools.partial(K.select_groups, interpret=interpret)

    def call(table, ge, gs):
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or mesh.size == 1:
            return kernel(table, ge, gs)
        rows = P(mesh.axis_names)
        return jax.shard_map(kernel, mesh=mesh, in_specs=(P(), rows, rows),
                             out_specs=rows, check_vma=False)(table, ge, gs)

    fn = custom_batching.custom_vmap(call)

    @fn.def_vmap
    def _rule(axis_size, in_batched, table, ge, gs):
        b_tab, b_ge, b_gs = in_batched
        if b_tab:
            raise NotImplementedError(
                "prefix_select_gather: a batched packed table is not "
                "supported — the vmapped axis must share one "
                "(workload-stacked) table")
        B = axis_size

        def flat(x, batched):
            x = x if batched else jnp.broadcast_to(x, (B,) + x.shape)
            return x.reshape((-1,) + x.shape[2:])

        out = call(table, flat(ge, b_ge), flat(gs, b_gs))
        return out.reshape((B, -1) + out.shape[1:]), True

    return fn


@functools.lru_cache(maxsize=None)
def _grouped_fn(interpret: bool):
    """The custom_vmap-wrapped grouped kernel call: ``(table, gids [G],
    ge [G, N, C], gs [G, N, C]) -> [G, N, 128]``."""
    kernel = functools.partial(K.select_groups_grouped, interpret=interpret)

    def call(table, gids, ge, gs):
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty or mesh.size == 1:
            return kernel(table, gids, ge, gs)
        groups = P(mesh.axis_names)
        return jax.shard_map(kernel, mesh=mesh,
                             in_specs=(P(), groups, groups, groups),
                             out_specs=groups, check_vma=False)(
                                 table, gids, ge, gs)

    fn = custom_batching.custom_vmap(call)

    @fn.def_vmap
    def _rule(axis_size, in_batched, table, gids, ge, gs):
        if in_batched[0]:
            raise NotImplementedError(
                "prefix_select_gather_blocked: the layer-blocked table "
                "must be unbatched")
        B = axis_size

        def merge(x, batched):  # [B, G, ...] -> [G * B, ...]
            x = x if batched else jnp.broadcast_to(x, (B,) + x.shape)
            x = jnp.swapaxes(x, 0, 1)
            return x.reshape((-1,) + x.shape[2:])

        G = ge.shape[1] if in_batched[2] else ge.shape[0]
        out = fn(table, *(merge(x, b) for x, b in
                          zip((gids, ge, gs), in_batched[1:])))
        return jnp.swapaxes(out.reshape((G, B) + out.shape[1:]), 0, 1), True

    return fn


def _group_bounds(rows, start, end, split, t0, t1, layout):
    """Each slot's clipped tile range as two packed-table group indices
    (``ge``, ``gs``) of the split-K table its system picks."""
    R, L0, L1 = layout
    rows = rows.astype(jnp.int32)
    t0 = t0.astype(jnp.int32)[:, None]
    t1 = t1.astype(jnp.int32)[:, None]
    sp = (split == 1)[:, None]
    total = jnp.where(sp, t1, t0)
    s = jnp.clip(start.astype(jnp.int32), 0, total)
    e = jnp.clip(end.astype(jnp.int32), 0, total)
    base = jnp.where(sp, R * L0 + rows * L1, rows * L0)
    return base + e, base + s


def _recombine(out, C: int, nf: int):
    """The kernel's ``[P, 128]`` hi/lo rows as ``[P, C, nf]`` int64."""
    g = out.reshape(out.shape[0], K.GROUPS_PER_ROW, K.GROUP)[:, :C]
    dhi = g[..., :nf].astype(jnp.int64)
    dlo = g[..., nf:2 * nf].astype(jnp.int64)
    return (dhi << K.LO_BITS) + dlo


@functools.partial(jax.jit, static_argnames=("layout", "nf"))
def prefix_select_gather_blocked(table, block, rows, start, end, split, t0,
                                 t1, *, layout, nf: int = 5):
    """:func:`prefix_select_gather` from block ``block`` (a scalar) of a
    layer-blocked ``[B, rows, 128]`` table whose blocks share
    ``layout``; ``rows`` index within the block. Bit-equal to the int64
    reference gather of that block's tables."""
    ge, gs = _group_bounds(rows, start, end, split, t0, t1, layout)
    out = _grouped_fn(jax.default_backend() != "tpu")(
        table, jnp.reshape(block, (1,)), ge[None], gs[None])
    return _recombine(out[0], rows.shape[1], nf)


@functools.partial(jax.jit, static_argnames=("layout", "nf"))
def prefix_select_gather(table, rows, start, end, split, t0, t1, *,
                         layout, nf: int = 5):
    """Split-selected per-slot prefix differences, ``[P, C, nf]`` int64.

    Args:
      table, layout: :func:`~repro.kernels.prefix_gather.kernel
        .pack_tables` output for the two split-K stacks.
      rows: ``[P, C]`` table row per chiplet slot. Rows carry any
        workload-stack offset (``((wi*A + a)*S + s)*3 + d``) already.
      start/end: ``[P, C]`` unclipped tile ranges.
      split: ``[P]`` per-system split-K selector (1 selects table 1).
      t0/t1: ``[P]`` per-row true tile totals — ranges clip here, so
        bucket-padded tail entries are never read.

    Equal, bit for bit, to the int64 reference gather
    (:func:`~repro.kernels.prefix_gather.ref.prefix_select_ref`).
    Needs 64-bit types enabled."""
    ge, gs = _group_bounds(rows, start, end, split, t0, t1, layout)
    out = _select_fn(jax.default_backend() != "tpu")(table, ge, gs)
    return _recombine(out, rows.shape[1], nf)
