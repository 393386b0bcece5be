"""Partition-rule based sharding for parameter pytrees.

The reference delegates parameter layout to torch DDP/FSDP wrappers
(ray/train/torch/train_loop_utils.py:162,179-183). The TPU-native
formulation is declarative: a model ships an ordered list of
(path-regex -> PartitionSpec) rules; we map them over the param pytree to
NamedShardings and let GSPMD insert the collectives.
"""

from __future__ import annotations

import math
import re
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

PyTree = Any


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


_path_str = path_str  # pre-round-14 private name


class PartitionRules:
    """Ordered (regex, PartitionSpec) rules; first match wins.

    Specs may name axes that a given mesh doesn't have — those axis names
    are dropped at resolution time, so one rule set serves every mesh
    shape (a tensor='absent' mesh simply replicates that dimension).
    """

    def __init__(self, rules: Sequence[tuple[str, PartitionSpec]]):
        self._rules = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(self, path: str, mesh: Mesh | None = None) -> PartitionSpec:
        for pat, spec in self._rules:
            if pat.search(path):
                return _prune_spec(spec, mesh) if mesh is not None else spec
        return PartitionSpec()

    def shardings(self, tree: PyTree, mesh: Mesh) -> PyTree:
        return jax.tree_util.tree_map_with_path(
            lambda path, _: NamedSharding(
                mesh, self.spec_for(_path_str(path), mesh)
            ),
            tree,
        )

    def specs(self, tree: PyTree, mesh: Mesh | None = None) -> PyTree:
        return jax.tree_util.tree_map_with_path(
            lambda path, _: self.spec_for(_path_str(path), mesh), tree
        )


def _prune_spec(spec: PartitionSpec, mesh) -> PartitionSpec:
    """Drop axis names not present in (or of size 1 in) the mesh.

    Works for both concrete `Mesh` and `AbstractMesh` (whose .shape is a
    name->size mapping).
    """
    shape = dict(mesh.shape)
    have = {n for n, s in shape.items() if s > 1}

    def prune(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in have)
            return kept if kept else None
        return entry if entry in have else None

    return PartitionSpec(*(prune(e) for e in spec))


def add_axis_to_spec(spec: PartitionSpec, shape, mesh, axis: str
                     ) -> PartitionSpec:
    """Extend `spec` (already pruned to `mesh`) with `axis` on the first
    dimension of `shape` that divides evenly by the combined shard count
    — the ZeRO-style "also shard this leaf over the replica axis"
    transformation. Leaves already touching `axis`, scalars, and leaves
    with no evenly-divisible dimension come back unchanged (those stay
    replicated over `axis` and are counted by the caller's ~1/N memory
    assertion slack)."""
    sizes = dict(mesh.shape)
    n = sizes.get(axis, 1)
    if n <= 1 or not shape:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))

    def axes_of(entry):
        if entry is None:
            return ()
        if isinstance(entry, (tuple, list)):
            return tuple(entry)
        return (entry,)

    if any(axis in axes_of(e) for e in entries):
        return spec
    for i, dim in enumerate(shape):
        cur = axes_of(entries[i])
        already = math.prod(sizes.get(a, 1) for a in cur)
        if dim % (already * n) == 0:
            entries[i] = cur + (axis,) if cur else axis
            return PartitionSpec(*entries)
    return spec


def shard_pytree(tree: PyTree, rules: PartitionRules, mesh: Mesh) -> PyTree:
    """Device-put `tree` with shardings derived from `rules`."""
    shardings = rules.shardings(tree, mesh)
    return jax.device_put(tree, shardings)


def constrain(x: jax.Array, *spec_entries) -> jax.Array:
    """with_sharding_constraint that tolerates axes missing from the
    ambient mesh (so model code can always write the full logical spec)."""
    mesh = _current_mesh()
    if mesh is None:
        return x
    spec = _prune_spec(PartitionSpec(*spec_entries), mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def _current_mesh():
    """The ambient mesh, if model code runs under `jax.set_mesh(mesh)`;
    None otherwise (single-device paths)."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None
