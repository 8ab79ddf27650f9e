"""Sharding rules: param, optimizer, activation, and cache partitioning.

Strategy (DP x TP/EP with FSDP-style weight sharding):
  * batch dims           -> ('pod', 'data')        (pure DP; 'pod' = DCN)
  * heads / d_ff / vocab / experts -> 'model'      (TP / EP)
  * the remaining large weight dim -> 'data'       (FSDP; ZeRO-1 falls out
    because optimizer moments mirror param specs leaf-for-leaf)
  * decode caches: sequence axis -> 'model'        (flash-decode: XLA
    turns softmax over the sharded axis into tiny max/sum all-reduces)
  * residual stream between layers -> seq over 'model' (Megatron-style SP,
    set via ``activation_policy``) so remat'd scan carries stay small.

Every rule is *divisibility-aware*: an axis that does not divide a dim is
dropped (replicated) rather than erroring — e.g. internvl2's vocab 92553
stays unsharded while its d_model shards.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import data_axes

# Sentinel for "the DP axes of whatever mesh we're on"
DATA = "__data__"


# ---------------------------------------------------------------------------
# Divisibility-aware spec fitting
# ---------------------------------------------------------------------------


def _resolve_axis(entry, mesh) -> Optional[Tuple[str, ...]]:
    if entry is None:
        return None
    if entry == DATA:
        axes = data_axes(mesh)
        return axes if axes else None
    if isinstance(entry, str):
        return (entry,) if entry in mesh.axis_names else None
    return tuple(a for a in entry if a in mesh.axis_names) or None


def fit_spec(shape: Sequence[int], spec: Sequence, mesh: Mesh) -> P:
    """Resolve DATA, drop missing mesh axes and non-dividing entries."""
    out = []
    used = set()
    for dim, entry in zip(shape, spec):
        axes = _resolve_axis(entry, mesh)
        if axes is None:
            out.append(None)
            continue
        axes = tuple(a for a in axes if a not in used)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        if size > 1 and dim % size == 0:
            out.append(axes if len(axes) > 1 else axes[0])
            used.update(axes)
        elif len(axes) > 1:
            # try the largest single axis that divides
            picked = None
            for a in sorted(axes, key=lambda a: -mesh.shape[a]):
                if dim % mesh.shape[a] == 0:
                    picked = a
                    break
            out.append(picked)
            if picked:
                used.add(picked)
        else:
            out.append(None)
    out += [None] * (len(shape) - len(out))
    return P(*out)


# ---------------------------------------------------------------------------
# Parameter rules (matched on the leaf's path suffix)
# ---------------------------------------------------------------------------

# name -> spec by ndim (stacked layer params carry a leading L axis = None)
_PARAM_RULES = [
    # embeddings / heads — vocab over 'model' only: sharding their D dim
    # over 'data' would conflict with batch-over-'data' in the loss einsum
    # and force batch replication of the hidden states.
    (r"embed$", {2: ("model", None)}),
    (r"lm_head$", {2: (None, "model")}),
    # attention
    (r"(wq|wk|wv)$", {3: (None, DATA, "model")}),
    (r"(bq|bk|bv)$", {2: (None, "model")}),
    (r"wo$", {3: (None, "model", DATA)}),
    # MLA
    (r"(w_dq|w_dkv)$", {3: (None, DATA, None)}),
    (r"(w_uq|w_uk|w_uv)$", {3: (None, None, "model")}),
    # FFN (dense 3d, MoE experts 4d: (L, E, D, F))
    (r"(w_gate|w_up)$", {3: (None, DATA, "model"),
                         4: (None, "model", DATA, None)}),
    (r"w_down$", {3: (None, "model", DATA),
                  4: (None, "model", None, DATA)}),
    (r"router$", {3: (None, DATA, None)}),
    # rwkv time/channel mix
    (r"(w_r|w_k|w_v|w_g)$", {3: (None, DATA, "model")}),
    (r"w_o$", {3: (None, "model", DATA)}),
    (r"(lora_a|decay_a)$", {3: (None, DATA, None)}),
    # rglru
    (r"(w_in)$", {3: (None, DATA, "model")}),
    (r"w_out$", {3: (None, "model", DATA)}),
    (r"conv_w$", {3: (None, None, "model")}),
    (r"(conv_b|gate_a_b|gate_x_b|lam)$", {2: (None, "model")}),
    (r"(gate_a|gate_x)$", {4: (None, "model", None, None)}),
]


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def param_spec_for(name: str, shape: Sequence[int], mesh: Mesh) -> P:
    for pattern, by_ndim in _PARAM_RULES:
        if re.search(pattern, name):
            spec = by_ndim.get(len(shape))
            if spec is not None:
                return fit_spec(shape, spec, mesh)
    # default: shard the two largest dims over (model, data) if they divide
    if len(shape) >= 2 and shape[-1] * shape[-2] >= 1 << 20:
        return fit_spec(shape, (None,) * (len(shape) - 2) + (DATA, "model"),
                        mesh)
    return P()


def param_specs(params: Any, mesh: Mesh) -> Any:
    """Pytree of PartitionSpec matching ``params``."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    specs = [param_spec_for(_leaf_name(path), leaf.shape, mesh)
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(tdef, specs)


# Serving layout overrides: decode batches are tiny, so expert weights
# keep D full and shard the FFN dim over the dp axes — gate/up matmuls
# become comm-free and only w_down's output needs one small activation
# all-reduce per MoE layer (instead of gathering GBs of expert weights).
_SERVING_OVERRIDES = [
    (r"(w_gate|w_up)$", {4: (None, "model", None, DATA)}),
    (r"w_down$", {4: (None, "model", DATA, None)}),
]


def param_specs_serving(params: Any, mesh: Mesh) -> Any:
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        name = _leaf_name(path)
        spec = None
        for pattern, by_ndim in _SERVING_OVERRIDES:
            if re.search(pattern, name) and len(leaf.shape) in by_ndim:
                spec = fit_spec(leaf.shape, by_ndim[len(leaf.shape)], mesh)
                break
        specs.append(spec if spec is not None
                     else param_spec_for(name, leaf.shape, mesh))
    return jax.tree_util.tree_unflatten(tdef, specs)


def param_shardings(params: Any, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(params, mesh))


# ---------------------------------------------------------------------------
# Batch / activation / cache specs
# ---------------------------------------------------------------------------


def batch_specs(batch: Any, mesh: Mesh) -> Any:
    """tokens/labels (B, S) -> (DATA, None); embeds (B, P, D) -> + None."""
    def spec(path, leaf):
        shape = leaf.shape
        return fit_spec(shape, (DATA,) + (None,) * (len(shape) - 1), mesh)
    flat, tdef = jax.tree_util.tree_flatten_with_path(batch)
    return jax.tree_util.tree_unflatten(
        tdef, [spec(p, l) for p, l in flat])


_CACHE_RULES = [
    # stacked KV caches (L, B, T, KV, Dh): seq over model (flash-decode)
    (5, (None, DATA, "model", None, None)),
    # MLA latent (L, B, T, R) / rwkv states (L, B, H, Dk) etc.
    (4, (None, DATA, "model", None)),
    (3, (None, DATA, "model")),
    (2, (None, DATA)),
    (1, (DATA,)),
]


def cache_spec_for(name: str, shape: Sequence[int], mesh: Mesh) -> P:
    if re.search(r"wkv$", name) and len(shape) == 5:
        # rwkv state (L, B, H, Dk, Dv): no seq axis; shard heads if possible
        return fit_spec(shape, (None, DATA, "model", None, None), mesh)
    if re.search(r"conv$", name) and len(shape) == 4:
        # (L, B, K-1, W): channel axis over model
        return fit_spec(shape, (None, DATA, None, "model"), mesh)
    for ndim, spec in _CACHE_RULES:
        if len(shape) == ndim:
            return fit_spec(shape, spec, mesh)
    return P()


def cache_specs(cache: Any, mesh: Mesh) -> Any:
    flat, tdef = jax.tree_util.tree_flatten_with_path(cache)
    return jax.tree_util.tree_unflatten(
        tdef, [cache_spec_for(_leaf_name(p), l.shape, mesh)
               for p, l in flat])


def opt_state_specs(opt_state: Any, pspecs: Any) -> Any:
    """Optimizer moments mirror param specs (ZeRO-1); step is replicated."""
    from repro.optim.adamw import AdamWState
    return AdamWState(step=P(), mu=pspecs, nu=pspecs)


# ---------------------------------------------------------------------------
# Activation policy (residual-stream constraint inside scan bodies)
# ---------------------------------------------------------------------------

_policy = threading.local()


@contextlib.contextmanager
def activation_policy(mesh: Mesh, *, seq_axis: Optional[str] = "model",
                      shard_residual_seq: bool = True):
    """While active, :func:`constrain_residual` pins the (B, S, D) residual
    stream to (DATA, seq_axis, None) — Megatron-style sequence sharding of
    the layer boundary, which keeps remat'd scan carries 1/|model| sized."""
    prev = getattr(_policy, "value", None)
    dp = data_axes(mesh)
    _policy.value = {
        "mesh": mesh,
        "spec": (dp if dp else None,
                 seq_axis if shard_residual_seq else None,
                 None),
    }
    try:
        yield
    finally:
        _policy.value = prev


def active_mesh() -> Optional[Mesh]:
    """The mesh of the active activation policy (None outside steps)."""
    pol = getattr(_policy, "value", None)
    return None if pol is None else pol["mesh"]


def constrain_residual(x):
    """Apply the active residual-stream constraint (no-op outside policy)."""
    pol = getattr(_policy, "value", None)
    if pol is None or x.ndim != 3:
        return x
    mesh = pol["mesh"]
    spec = fit_spec(x.shape, pol["spec"], mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain(x, spec_template: Sequence) -> Any:
    pol = getattr(_policy, "value", None)
    if pol is None:
        return x
    mesh = pol["mesh"]
    spec = fit_spec(x.shape, spec_template, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Scenario-axis sharding (pathfinding sweeps)
# ---------------------------------------------------------------------------


def scenario_mesh(min_devices: int = 2,
                  n_cells: Optional[int] = None) -> Optional[Mesh]:
    """1-D ``('data',)`` mesh over the local devices for sharding a
    scenario (deployment grid) axis — e.g. the stacked
    :class:`repro.pathfinding.device.ScenarioEngine` scan.

    With ``n_cells`` the mesh takes the largest number of devices that
    divides the cell count (10 cells on 4 devices -> 2 devices), so the
    scenario axis is always really split. Returns ``None`` when that
    leaves fewer than ``min_devices`` devices (sharding a single device
    only adds dispatch overhead). On CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the
    first jax import to expose N virtual devices."""
    from repro.launch.mesh import _mesh_kwargs

    devices = jax.devices()
    n = len(devices)
    if n_cells is not None:
        n = max(d for d in range(1, n + 1) if n_cells % d == 0)
    if n < min_devices:
        return None
    return jax.make_mesh((n,), ("data",), devices=devices[:n],
                         **_mesh_kwargs(1))


def shard_scenarios(arrays: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """Place each array with its *leading* (scenario) axis split over the
    mesh's data axes. The split is required: a leading axis the mesh
    does not divide raises instead of silently replicating every cell
    on every device (:func:`scenario_mesh` sizes a mesh that divides)."""
    out = {}
    for k, x in arrays.items():
        spec = fit_spec(x.shape, (DATA,) + (None,) * (x.ndim - 1), mesh)
        if mesh.size > 1 and spec[0] is None:
            raise ValueError(
                f"scenario axis of {k!r} ({x.shape[0]} cells) does not "
                f"divide the {mesh.size}-device mesh")
        out[k] = jax.device_put(x, NamedSharding(mesh, spec))
    return out
