"""Logical-axis sharding rules: one mapping from logical tensor axes to mesh
axes, consumed by the LM substrate (models, launch).

A :class:`ShardingRules` turns logical axis names ("batch", "embed",
...) into :class:`~jax.sharding.PartitionSpec` entries against a
concrete mesh.  The mapping is scheme-based: ``_BASE`` holds the
tensor-parallel default and ``_SCHEMES`` holds named overrides (fsdp, ...).
Rules are pure metadata — constructing them never touches device state, and
`spec` silently drops mesh axes the mesh doesn't have (so one mapping
serves 1-D test meshes, 2-D single-pod meshes, and 3-D multi-pod meshes).

Usage::

    rules = make_rules(mesh, scheme="fsdp")
    w_spec = rules.spec("embed", "ffn")        # PartitionSpec for a weight
    x = rules.constrain(x, "batch", None, "embed")   # sharding constraint

The sharded graph plans do not use it: their one plan body
(`repro.dist.operator.plan_from_form`) splits every per-shard array on its
leading axis and every signal on its vertex (last) axis over the mesh's
1-D axis.  :func:`auto_mesh` below is theirs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Mapping, Optional, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

AxisTarget = Union[str, Tuple[str, ...], None]

# Scheme-independent logical-axis vocabulary with the tensor-parallel
# (megatron-style) defaults: batch over the data axes, weight matrices
# column/row split over 'model', everything else replicated.
_BASE: Dict[str, AxisTarget] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    "frames": None,
    "moe_group": "data",
    # weights
    "layers": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "kv_lora": None,
    "ffn": "model",
    "state": None,
    "expert": "model",
    "vocab": "model",
}

# Named scheme overrides applied on top of _BASE.
_SCHEMES: Dict[str, Dict[str, AxisTarget]] = {
    # tensor parallel (the _BASE defaults)
    "default": {},
    "tp": {},
    # fully-sharded data parallel: weights sharded over every mesh axis on
    # their embed dimension, activations batch-sharded over every axis, no
    # tensor parallelism on heads/ffn/vocab; MoE keeps expert parallelism.
    "fsdp": {
        "batch": ("pod", "data", "model"),
        "embed": ("data", "model"),
        "heads": None,
        "kv_heads": None,
        "ffn": None,
        "vocab": None,
        "expert": "model",
        "moe_group": "data",
    },
    # fsdp without expert parallelism (dense-expert debugging scheme)
    "fsdp_noep": {
        "batch": ("pod", "data", "model"),
        "embed": ("data", "model"),
        "heads": None,
        "kv_heads": None,
        "ffn": None,
        "vocab": None,
        "expert": None,
        "moe_group": "data",
    },
}


@dataclasses.dataclass
class ShardingRules:
    """Logical-axis -> mesh-axis mapping bound to a mesh (or to None = no-op).

    ``mapping`` values may be a mesh axis name, a tuple of mesh axis names
    (sharded over their product), or None (replicated).  Mesh axes absent
    from the bound mesh are dropped, and a mesh axis already consumed by an
    earlier dimension of the same spec is dropped too (a mesh axis can shard
    at most one dimension of a tensor).
    """

    mapping: Mapping[str, AxisTarget]
    mesh: Any = None

    @classmethod
    def null(cls) -> "ShardingRules":
        """Rules that replicate everything and make `constrain` a no-op."""
        return cls(mapping={}, mesh=None)

    def _mesh_axes(self) -> Tuple[str, ...]:
        return tuple(getattr(self.mesh, "axis_names", ()) or ())

    def spec(self, *logical_axes: Optional[str]) -> P:
        """PartitionSpec for a tensor whose dims carry these logical names."""
        available = self._mesh_axes()
        used: set = set()
        entries = []
        for name in logical_axes:
            target = self.mapping.get(name) if name is not None else None
            if target is None:
                entries.append(None)
                continue
            if isinstance(target, str):
                target = (target,)
            live = [ax for ax in target if ax in available and ax not in used]
            used.update(live)
            if not live:
                entries.append(None)
            elif len(live) == 1:
                entries.append(live[0])
            else:
                entries.append(tuple(live))
        return P(*entries)

    def constrain(self, x, *logical_axes: Optional[str]):
        """with_sharding_constraint under the bound mesh (identity if none)."""
        if self.mesh is None or not self._mesh_axes():
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(*logical_axes)))


@functools.lru_cache(maxsize=None)
def auto_mesh(mesh=None, axis: str = "graph"):
    """`mesh` with every axis in Auto mode (None: a 1-D `axis` mesh over
    all devices).  The sharded graph backends index the vertex axis of
    shard_map outputs with global gathers, which Explicit-mode axes (the
    `jax.make_mesh` default) refuse to type."""
    from jax.sharding import AxisType, Mesh

    if mesh is None:
        mesh = jax.make_mesh((len(jax.devices()),), (axis,))
    auto = (AxisType.Auto,) * len(mesh.axis_names)
    if tuple(getattr(mesh, "axis_types", auto)) == auto:
        return mesh
    return Mesh(mesh.devices, mesh.axis_names, axis_types=auto)


@functools.lru_cache(maxsize=None)
def make_rules(mesh, scheme: str = "default") -> ShardingRules:
    """Build the rules for a named scheme bound to `mesh` (cached)."""
    try:
        overrides = _SCHEMES[scheme]
    except KeyError:
        raise KeyError(
            f"unknown sharding scheme {scheme!r}; "
            f"available: {sorted(_SCHEMES)}") from None
    mapping = dict(_BASE)
    mapping.update(overrides)
    return ShardingRules(mapping=mapping, mesh=mesh)
