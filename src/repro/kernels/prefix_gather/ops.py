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
    R, L0, L1 = layout
    rows = rows.astype(jnp.int32)
    t0 = t0.astype(jnp.int32)[:, None]
    t1 = t1.astype(jnp.int32)[:, None]
    sp = (split == 1)[:, None]
    total = jnp.where(sp, t1, t0)
    s = jnp.clip(start.astype(jnp.int32), 0, total)
    e = jnp.clip(end.astype(jnp.int32), 0, total)
    base = jnp.where(sp, R * L0 + rows * L1, rows * L0)
    out = _select_fn(jax.default_backend() != "tpu")(table, base + e,
                                                     base + s)
    P, C = rows.shape
    g = out.reshape(P, K.GROUPS_PER_ROW, K.GROUP)[:, :C]
    dhi = g[..., :nf].astype(jnp.int64)
    dlo = g[..., nf:2 * nf].astype(jnp.int64)
    return (dhi << K.LO_BITS) + dlo
