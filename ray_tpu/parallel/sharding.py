"""Logical-axis sharding rules (GSPMD style).

The reference delegates sharded-weights strategies to torch FSDP /
DeepSpeed inside the worker loop (train/torch/train_loop_utils.py
prepare_model); here sharding is first-class: every parameter and
activation carries *logical* axis names, and a rule table maps logical
axes to mesh axes.  Changing parallelism = changing the rule table, never
the model code (the maxtext/scaling-book recipe).

Standard logical axes: "batch", "seq", "embed", "heads", "kv_heads",
"head_dim", "mlp", "vocab", "expert", "layers".
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# rule: logical axis -> mesh axis | tuple of mesh axes | None (replicated)
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

# Batch is split over every data-ish axis; fsdp additionally shards the
# weights' embed dim (ZeRO-3); tp shards heads/mlp/vocab (Megatron).
DEFAULT_RULES: Rules = {
    "batch": ("dp", "fsdp"),
    "seq": "sp",
    "embed": "fsdp",
    "heads": "tp",
    "kv_heads": "tp",
    "head_dim": None,
    "mlp": "tp",
    "vocab": "tp",
    "expert": "ep",
    "layers": None,
}


def spec_for(logical_axes: Sequence[Optional[str]],
             rules: Optional[Rules] = None,
             mesh: Optional[Mesh] = None) -> P:
    """PartitionSpec for a tensor's logical axes, dropping mesh axes the
    mesh doesn't have (so one rule table serves every mesh shape)."""
    rules = rules if rules is not None else DEFAULT_RULES
    have = set(mesh.axis_names) if mesh is not None else None
    used = set()
    out = []
    for ax in logical_axes:
        m = rules.get(ax) if ax is not None else None
        if m is None:
            out.append(None)
            continue
        parts = (m,) if isinstance(m, str) else tuple(m)
        parts = tuple(p for p in parts
                      if (have is None or p in have) and p not in used)
        used.update(parts)
        if not parts:
            out.append(None)
        elif len(parts) == 1:
            out.append(parts[0])
        else:
            out.append(parts)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_specs(logical_tree: Any, rules: Optional[Rules] = None,
               mesh: Optional[Mesh] = None) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: spec_for(axes, rules, mesh),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


def tree_shardings(logical_tree: Any, mesh: Mesh,
                   rules: Optional[Rules] = None) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        tree_specs(logical_tree, rules, mesh),
                        is_leaf=lambda x: isinstance(x, P))


def constrain(x, logical_axes: Sequence[Optional[str]],
              rules: Optional[Rules] = None,
              mesh: Optional[Mesh] = None):
    """Sharding constraint by logical names (inside jit)."""
    mesh = mesh or _current_mesh()
    if mesh is None or _mesh_trivial(mesh):
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec_for(logical_axes, rules, mesh)))


def shard_count(logical_axis: str, rules: Optional[Rules] = None,
                mesh: Optional[Mesh] = None) -> int:
    """How many ways `constrain` splits a logical axis on the (ambient)
    mesh; 1 without a mesh.  For code that must size a block by what ONE
    device holds."""
    mesh = mesh or _current_mesh()
    if mesh is None:
        return 1
    spec = spec_for((logical_axis,), rules, mesh)
    parts = spec[0] if len(spec) else ()
    return math.prod(mesh.shape[a] for a in
                     ((parts,) if isinstance(parts, str) else parts))


def _mesh_trivial(mesh: Mesh) -> bool:
    return math.prod(mesh.shape.values()) == 1


_MESH_STACK = []


class use_mesh:
    """Context manager setting the ambient mesh for `constrain`."""

    def __init__(self, mesh: Mesh) -> None:
        self.mesh = mesh

    def __enter__(self):
        _MESH_STACK.append(self.mesh)
        return self.mesh

    def __exit__(self, *a):
        _MESH_STACK.pop()


def _current_mesh() -> Optional[Mesh]:
    return _MESH_STACK[-1] if _MESH_STACK else None
