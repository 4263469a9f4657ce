"""Logical-axis sharding rules (MaxText-style) for the (pod, data, model)
production mesh.

Tensors are annotated with *logical* axis names; a :class:`ShardingRules`
table maps each logical name to zero or more mesh axes.  Changing the table
re-shards the whole model — this is the knob the beyond-paper sharding
autotuner (DESIGN.md §7.1) searches over, and how single-pod vs multi-pod
meshes reuse one model definition (``batch`` → ('data',) or
('pod', 'data')).

A logical dim is only sharded if its size divides the product of the mapped
mesh axes — otherwise it silently falls back to replication (e.g. MQA's
kv_heads=1 across a 16-way model axis).
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "DEFAULT_RULES", "MULTIPOD_RULES", "logical_spec",
           "constrain", "mesh_axis_size"]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name → tuple of mesh axis names (() = replicate)."""
    table: Mapping[str, tuple[str, ...]]

    def axes_for(self, name: str | None) -> tuple[str, ...]:
        if name is None:
            return ()
        return tuple(self.table.get(name, ()))

    def replace(self, **updates: tuple[str, ...]) -> "ShardingRules":
        t = dict(self.table)
        for k, v in updates.items():
            t[k] = tuple(v)
        return ShardingRules(t)


_BASE_TABLE = {
    # activations
    "batch": ("data",),
    "batch_attn": ("data",),     # attention-region batch (may add 'model'
                                 # when heads don't divide the TP axis)
    "seq": (),                   # sharded for long-context cells (SP)
    "kv_seq": (),
    "embed": (),                 # d_model on activations: replicated
    "heads": ("model",),
    "kv_heads": ("model",),
    # params — TP axis per Megatron; FSDP axis shards the complement
    "vocab": ("model",),
    "embed_fsdp": ("data",),     # FSDP dim of weight matrices
    "mlp": ("model",),           # d_ff / column-parallel out dim
    "qkv": ("model",),
    "o_in": ("model",),          # row-parallel in dim
    "experts": ("model",),       # EP
    "expert_cap": (),            # capacity/slot parallelism fallback
    "expert_mlp": (),            # within-expert width (EP precludes TP here)
    "ssm_inner": ("model",),
    "lora": (),
    "conv": (),
    "norm": (),
    "state": (),
}

DEFAULT_RULES = ShardingRules(dict(_BASE_TABLE))
MULTIPOD_RULES = DEFAULT_RULES.replace(batch=("pod", "data"),
                                       embed_fsdp=("data",))


def mesh_axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def logical_spec(rules: ShardingRules, mesh: Mesh,
                 names: Sequence[str | None],
                 dims: Sequence[int] | None = None) -> P:
    """PartitionSpec from logical names; non-divisible dims replicate.

    A mesh axis shards at most one positional dim: an axis already taken by
    an earlier dim is dropped from later ones (e.g. FSDP's 'data' on a
    weight's embed dim after the batch dim of a gathered activation)."""
    parts = []
    used: set[str] = set()
    for i, name in enumerate(names):
        axes = tuple(a for a in rules.axes_for(name) if a not in used)
        if not axes:
            parts.append(None)
            continue
        if dims is not None:
            # progressively drop trailing axes until the dim divides —
            # e.g. batch=('data','model') degrades to ('data',) for small B
            while axes:
                size = mesh_axis_size(mesh, axes)
                if size > 1 and dims[i] % size == 0:
                    break
                axes = axes[:-1]
            if not axes:
                parts.append(None)
                continue
        used.update(axes)
        parts.append(axes if len(axes) > 1 else axes[0])
    # trailing Nones can be dropped but keep explicit for readability
    return P(*parts)


def constrain(x, rules: ShardingRules, mesh: Mesh, *names: str | None):
    """with_sharding_constraint by logical names (no-op off-mesh)."""
    if mesh is None or mesh.empty:
        return x
    spec = logical_spec(rules, mesh, names, dims=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
