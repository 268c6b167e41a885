"""Logical-axis → mesh-axis sharding rules (DP / FSDP / TP / SP / EP).

Every parameter carries a tuple of logical axis names (see models/module.py);
this module maps them to ``PartitionSpec``s for a concrete mesh. Rules are a
plain dict, so a caller can override one per architecture.

Divisibility guard: a mesh axis is only assigned when it evenly divides the
dimension — otherwise the dim falls back to replication. This is what makes
one rule table serve all 10 archs (9-head GQA, 73448-vocab, batch=1
long-context cells, ...) without per-arch special cases.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# Default logical rules for the production (data, model) / (pod, data, model)
# meshes. FSDP over 'data' (params gathered per-layer under scan), TP over
# 'model', EP over 'model' for experts.
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "embed": ("data",),
    "mlp": ("model",),
    "heads": ("model",),
    "kv": ("model",),
    "vocab": ("model",),
    "expert": ("model",),
    "lora": (None,),
}


def _mesh_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def spec_for(axes: Tuple[Optional[str], ...], shape: Tuple[int, ...],
             mesh: Mesh, rules: Optional[Dict] = None) -> P:
    rules = rules or DEFAULT_RULES
    used = set()
    out = []
    for dim, name in zip(shape, axes):
        assigned = None
        if name is not None:
            for mesh_axis in rules.get(name, (None,)):
                if mesh_axis is None or mesh_axis in used:
                    continue
                if mesh_axis not in mesh.axis_names:
                    continue
                if dim % _mesh_size(mesh, mesh_axis) == 0:
                    assigned = mesh_axis
                    used.add(mesh_axis)
                    break
        out.append(assigned)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_shardings(axes_tree, shape_tree, mesh: Mesh, rules=None):
    """Pytree of NamedShardings matching the params tree."""
    def one(axes, shp):
        return NamedSharding(mesh, spec_for(tuple(axes), shp.shape, mesh,
                                            rules))
    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and
                        all(isinstance(e, (str, type(None))) for e in x))


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes that carry the batch dim (pure DP across pods)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def batch_sharding(mesh: Mesh, ndim: int, batch_size: int,
                   seq_axis: Optional[str] = None, seq_len: int = 0) -> NamedSharding:
    """Batch sharded over the data axes (divisibility-guarded); optional
    sequence sharding (SP) on dim 1."""
    dp = data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    first = dp if batch_size % dp_size == 0 else None
    rest = [None] * (ndim - 1)
    if seq_axis and ndim > 1 and seq_len % _mesh_size(mesh, seq_axis) == 0:
        rest[0] = seq_axis
    return NamedSharding(mesh, P(first, *rest))


def cache_sharding(mesh: Mesh, shape, batch_size: int) -> NamedSharding:
    """KV caches: batch over data axes, seq (dim 1) over 'model'.

    Falls back per-dim when sizes don't divide (e.g. batch=1 long-context:
    everything hangs off the seq dim instead)."""
    dp = data_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    spec = [None] * len(shape)
    if len(shape) >= 2:
        if batch_size % dp_size == 0:
            spec[0] = dp
            if shape[1] % _mesh_size(mesh, "model") == 0:
                spec[1] = "model"
        else:
            # batch too small: shard seq over both axes if possible
            if shape[1] % (dp_size * _mesh_size(mesh, "model")) == 0:
                spec[1] = tuple(dp) + ("model",)
            elif shape[1] % _mesh_size(mesh, "model") == 0:
                spec[1] = "model"
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
