"""Spec utilities: fit ideal PartitionSpecs to a concrete mesh.

``fit_specs`` walks a (shapes, specs) pytree pair and drops any spec axis
that (a) references a mesh axis absent from the mesh, or (b) does not evenly
divide the corresponding tensor dimension.  This lets model code declare the
*ideal* layout once (e.g. KV heads over the model axis) while MQA configs,
tiny smoke configs, and the 1-device CPU runtime all degrade gracefully to
replication on that axis.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _axis_size(mesh: Mesh, axis: Union[str, Tuple[str, ...]]) -> int:
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return int(mesh.shape[axis])


def fit_spec(shape: Sequence[int], spec: P, mesh: Mesh) -> P:
    """Drop spec entries that don't exist in / divide over the mesh."""
    names = set(mesh.axis_names)
    out = []
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    for dim, ax in zip(shape, entries):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        kept = tuple(a for a in axes if a in names)
        if not kept:
            out.append(None)
            continue
        size = _axis_size(mesh, kept)
        if dim % size != 0:
            # Try progressively smaller prefixes of the axis tuple.
            while kept and dim % _axis_size(mesh, kept) != 0:
                kept = kept[:-1]
            out.append(kept if kept else None)
            continue
        out.append(kept if len(kept) > 1 else kept[0])
    return P(*out)


def fit_specs(shapes: Any, specs: Any, mesh: Mesh) -> Any:
    """Tree-map :func:`fit_spec` over matching (shape, spec) pytrees."""

    def one(shape_leaf, spec_leaf):
        shape = (
            shape_leaf.shape if hasattr(shape_leaf, "shape") else tuple(shape_leaf)
        )
        return fit_spec(shape, spec_leaf, mesh)

    return jax.tree.map(
        one, shapes, specs, is_leaf=lambda x: isinstance(x, P)
    )


def tree_bytes(tree: Any) -> int:
    """Total bytes of a pytree of arrays/ShapeDtypeStructs."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    return total
