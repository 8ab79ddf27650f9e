"""Sharded checkpointing: per-leaf ``.npy`` shards + a JSON manifest.

Design (offline container — no orbax/tensorstore):

  * Every pytree leaf is saved as one or more ``.npy`` shard files, split
    along its largest axis into ``n_shards`` pieces so that (a) hosts write
    in parallel on a real cluster, and (b) restore can re-assemble onto a
    DIFFERENT mesh — the manifest stores only the logical array, not the
    device layout, which is what makes restarts elastic (restore onto
    more or fewer devices than saved from).
  * The manifest (checkpoint.json) records the tree structure, per-leaf
    dtype/shape/shard files, the step, and a payload checksum; writes are
    atomic (tmp dir + rename) so a failure mid-save never corrupts the
    latest valid checkpoint.
  * ``CheckpointManager`` keeps the last ``keep`` checkpoints and finds
    the newest valid one on restart; ``restore`` prunes directories whose
    payload fails verification (a torn non-atomic copy must not poison
    restart) and falls back to the next-newest valid step.
  * Pytrees may contain *checkpointable objects* — anything exposing
    ``checkpoint_arrays() -> dict[str, ndarray]`` and
    ``from_checkpoint_arrays(dict) -> object`` (e.g.
    :class:`repro.pathfinding.pareto.ParetoArchive`). They are expanded
    to their array dict on save and reconstituted on load; their array
    shapes are *elastic* (a restored archive may hold a different number
    of rows than the template). The :data:`ELASTIC` sentinel marks any
    other template leaf whose shape should be taken from the manifest
    instead of the template (e.g. a grow-only history vector).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MANIFEST = "checkpoint.json"


class CorruptCheckpointError(ValueError):
    """The checkpoint payload is unreadable or fails verification
    (missing/truncated shard, unreadable manifest, checksum mismatch) —
    as opposed to a *valid* checkpoint that is structurally incompatible
    with the template (missing leaf / shape mismatch), which raises
    ``KeyError``/``ValueError`` and is never silently pruned."""


class _Elastic:
    """Template sentinel: restore this leaf with the manifest's shape and
    dtype instead of requiring the template's."""

    def __repr__(self) -> str:  # pragma: no cover - repr only
        return "ELASTIC"


ELASTIC = _Elastic()


def _is_checkpointable(x: Any) -> bool:
    return (hasattr(x, "checkpoint_arrays")
            and hasattr(x, "from_checkpoint_arrays"))


def _expand_for_save(tree: Any) -> Any:
    """Replace checkpointable objects with their array dicts (the dict
    becomes a subtree, so each array gets its own manifest leaf)."""
    return jax.tree_util.tree_map(
        lambda leaf: (dict(leaf.checkpoint_arrays())
                      if _is_checkpointable(leaf) else leaf),
        tree, is_leaf=_is_checkpointable)


def _expand_for_load(tree: Any) -> Any:
    """Template twin of :func:`_expand_for_save`: every object array is
    marked :data:`ELASTIC` (its saved shape wins over the template's)."""
    return jax.tree_util.tree_map(
        lambda leaf: ({k: ELASTIC for k in leaf.checkpoint_arrays()}
                      if _is_checkpointable(leaf) else leaf),
        tree, is_leaf=_is_checkpointable)


def _collapse(like: Any, restored: Any) -> Any:
    """Reconstitute objects: where ``like`` holds a checkpointable leaf,
    ``restored`` holds its array-dict subtree."""
    return jax.tree_util.tree_map(
        lambda leaf, sub: (leaf.from_checkpoint_arrays(sub)
                           if _is_checkpointable(leaf) else sub),
        like, restored, is_leaf=_is_checkpointable)


def _leaf_paths(tree: Any) -> List[Tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out.append((name, leaf))
    return out


def _shard_slices(shape: Tuple[int, ...], n_shards: int):
    """Split along the largest axis into up to n_shards contiguous slices."""
    if not shape or n_shards <= 1:
        return [tuple(slice(None) for _ in shape)]
    axis = int(np.argmax(shape))
    n = min(n_shards, shape[axis])
    edges = np.linspace(0, shape[axis], n + 1, dtype=int)
    slices = []
    for lo, hi in zip(edges[:-1], edges[1:]):
        if hi > lo:
            s = [slice(None)] * len(shape)
            s[axis] = slice(int(lo), int(hi))
            slices.append(tuple(s))
    return slices


def _as_jnp(arr: np.ndarray):
    """Device conversion that preserves the manifest dtype exactly: a
    float64/int64 leaf must not silently demote to 32-bit when the
    process runs without global x64 (the search-state checkpoints are
    float64 end to end)."""
    from repro.jaxenv import search_numerics

    if arr.dtype in (np.float64, np.int64, np.uint64, np.complex128):
        with search_numerics():
            return jnp.asarray(arr)
    return jnp.asarray(arr)


def save_checkpoint(directory: str, step: int, tree: Any,
                    n_shards: int = 8) -> str:
    """Atomic save of a pytree. Returns the checkpoint path.

    The tree may contain checkpointable objects (see module docstring);
    they are expanded to their array dicts before writing."""
    tree = _expand_for_save(tree)
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)

    manifest: Dict[str, Any] = {"step": step, "leaves": {}, "time": 0.0}
    manifest["time"] = time.time()
    digest = hashlib.sha256()
    for name, leaf in _leaf_paths(tree):
        arr = np.asarray(jax.device_get(leaf))
        entry = {"dtype": str(arr.dtype), "shape": list(arr.shape),
                 "shards": []}
        for i, sl in enumerate(_shard_slices(arr.shape, n_shards)):
            fname = f"{name.replace('/', '.')}.{i}.npy"
            piece = np.ascontiguousarray(arr[sl])
            np.save(os.path.join(tmp, fname), piece)
            digest.update(piece.tobytes()[:4096])
            entry["shards"].append({
                "file": fname,
                "slices": [[s.start, s.stop] if s.start is not None
                           or s.stop is not None else None
                           for s in sl],
            })
        manifest["leaves"][name] = entry
    manifest["checksum"] = digest.hexdigest()
    with open(os.path.join(tmp, MANIFEST), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def load_checkpoint(path: str, like: Any,
                    sharding_fn=None) -> Tuple[int, Any]:
    """Restore into the structure of ``like``. ``sharding_fn(name, arr)``
    may return a jax.sharding.Sharding to place each leaf directly onto
    the *current* mesh (which may differ from the save-time mesh).

    Template leaves that are :data:`ELASTIC` (or arrays belonging to a
    checkpointable object) take their shape/dtype from the manifest.
    Unreadable payloads raise :class:`CorruptCheckpointError`; a valid
    checkpoint that does not fit the template raises ``KeyError`` /
    ``ValueError`` as before."""
    try:
        with open(os.path.join(path, MANIFEST)) as f:
            manifest = json.load(f)
        leaves = manifest["leaves"]
    except (OSError, ValueError, KeyError) as e:
        raise CorruptCheckpointError(
            f"checkpoint {path}: unreadable manifest ({e})") from e

    # read + digest EVERY manifest leaf in manifest (= save) order
    # before any template matching: the checksum covers the whole
    # payload, so verification must too — a template requesting a subset
    # of the saved leaves must not skew the digest into a false
    # corruption verdict (CheckpointManager.restore *prunes* on
    # corruption, so a false positive would destroy valid snapshots)
    digest = hashlib.sha256()
    arrays: Dict[str, np.ndarray] = {}
    for name, entry in leaves.items():
        arr = np.empty(entry["shape"], dtype=np.dtype(entry["dtype"]))
        for sh in entry["shards"]:
            try:
                piece = np.load(os.path.join(path, sh["file"]))
            except (OSError, ValueError) as e:
                raise CorruptCheckpointError(
                    f"checkpoint {path}: bad shard {sh['file']} ({e})"
                ) from e
            sl = tuple(slice(None) if s is None else slice(s[0], s[1])
                       for s in sh["slices"])
            try:
                arr[sl if sl else ...] = piece
            except ValueError as e:
                raise CorruptCheckpointError(
                    f"checkpoint {path}: shard {sh['file']} does not fit "
                    f"its manifest slice ({e})") from e
            digest.update(piece.tobytes()[:4096])
        arrays[name] = arr
    if manifest.get("checksum") and manifest["checksum"] != digest.hexdigest():
        raise CorruptCheckpointError(
            f"checkpoint {path} checksum mismatch (corrupt?)")

    like_x = _expand_for_load(like)
    names = [n for n, _ in _leaf_paths(like_x)]
    flat_like, tdef = jax.tree_util.tree_flatten(like_x)
    out = []
    for name, leaf in zip(names, flat_like):
        arr = arrays.get(name)
        if arr is None:
            raise KeyError(f"checkpoint missing leaf {name!r}")
        if (leaf is not ELASTIC
                and list(arr.shape) != list(np.shape(leaf))):
            raise ValueError(
                f"shape mismatch for {name}: ckpt {arr.shape} vs "
                f"model {np.shape(leaf)}")
        if sharding_fn is not None:
            sharding = sharding_fn(name, arr)
            out.append(jax.device_put(arr, sharding) if sharding is not None
                       else _as_jnp(arr))
        else:
            out.append(_as_jnp(arr))
    restored = jax.tree_util.tree_unflatten(tdef, out)
    return manifest["step"], _collapse(like, restored)


class CheckpointManager:
    """Rotating checkpoint directory with newest-valid discovery."""

    def __init__(self, directory: str, keep: int = 3, n_shards: int = 8):
        self.directory = directory
        self.keep = keep
        self.n_shards = n_shards
        os.makedirs(directory, exist_ok=True)

    def all_steps(self) -> List[int]:
        steps = []
        for d in os.listdir(self.directory):
            if d.startswith("step_") and not d.endswith(".tmp"):
                if os.path.exists(os.path.join(self.directory, d, MANIFEST)):
                    steps.append(int(d.split("_")[1]))
        return sorted(steps)

    def step_path(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:08d}")

    def latest(self) -> Optional[str]:
        steps = self.all_steps()
        if not steps:
            return None
        return self.step_path(steps[-1])

    def save(self, step: int, tree: Any) -> str:
        path = save_checkpoint(self.directory, step, tree, self.n_shards)
        for s in self.all_steps()[:-self.keep]:
            shutil.rmtree(self.step_path(s), ignore_errors=True)
        return path

    def restore(self, like: Any, sharding_fn=None) -> Tuple[int, Any]:
        """Restore the newest *valid* checkpoint.

        A directory whose payload fails verification (torn non-atomic
        copy, truncated shard, checksum mismatch) is pruned and the
        next-newest step is tried — previously a single corrupt copy
        poisoned every restart. Structural incompatibility with ``like``
        (missing leaf / shape mismatch) still raises immediately: that
        is a caller bug, not corruption."""
        steps = self.all_steps()
        if not steps:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        last_err: Optional[Exception] = None
        for s in reversed(steps):
            path = self.step_path(s)
            try:
                return load_checkpoint(path, like, sharding_fn)
            except CorruptCheckpointError as e:
                last_err = e
                shutil.rmtree(path, ignore_errors=True)
        raise FileNotFoundError(
            f"no valid checkpoint in {self.directory} "
            f"(every step failed verification; last: {last_err})")
