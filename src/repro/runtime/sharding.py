"""Logical-axis sharding rules (MaxText-style) for DP/FSDP/TP/EP/SP.

Every parameter/cache leaf carries a tuple of *logical* axis names (see
``models.common.ParamSpec``). A rule table maps logical names to mesh axes
with graceful fallback: an assignment is only used if the dimension size is
divisible by the mesh-axis product and no mesh axis is claimed twice within
one tensor; otherwise the next candidate (or replication) applies.

This fallback is what lets one rule table serve all 10 architectures — e.g.
``kv_heads`` takes the ``model`` axis when it divides (deepseek-7b, kv=32) and
otherwise the KV **sequence** dimension takes it instead (command-r, kv=8),
which is exactly sequence-parallel (flash-decoding style) cache sharding.
"""
from __future__ import annotations

from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Candidate mesh-axis assignments per logical axis, in priority order.
# Each candidate is a tuple of mesh axes the dim is sharded over (jointly).
DEFAULT_RULES: dict[str, tuple[tuple[str, ...], ...]] = {
    # data-parallel batch (pod-major so cross-pod traffic is pure DP)
    "batch": (("pod", "data"), ("data",), ()),
    # tensor parallel
    "vocab": (("model",), ()),
    "heads": (("model",), ()),
    "kv_heads": (("model",), ()),
    "mlp": (("model",), ()),
    "experts": (("model",), ()),
    "ssm_in": (("model",), ()),
    "ssm_inner": (("model",), ()),
    "ssm_conv": (("model",), ()),
    "ssm_heads": (("model",), ()),
    # FSDP: weight-stationary dims sharded over the data axis
    "embed": (("data",), ()),
    "src_embed": (("data",), ()),
    "vision_embed": (("data",), ()),
    "expert_mlp": (("data",), ()),   # second-choice FSDP dim for experts
    # sequence parallelism (activations / KV caches)
    "kv_seq": (("model",), ()),
    "seq": ((), ()),
    # always replicated
    "layers": ((),),
    "group": ((),),
    "embed_norm": ((),),
    "head_dim": ((),),
    "state": ((),),
    "conv": ((),),
    "router_in": ((),),
    "experts_in": ((),),
}

# Serving-time rules (§Perf A2/C1): weights are read-only at serve time, so
# FSDP-sharding their embed dims only forces a re-gather on every step.
# Replicating the embed dims leaves dense weights TP-only. Expert weights
# then store ``expert_mlp`` over the data axis (its next candidate); the MoE
# layer (models/moe.py::apply_moe) splits experts over the model axis only,
# so its ``shard_map`` boundary all-gathers that dim of each layer's expert
# weights on every step.
SERVING_RULES: dict[str, tuple[tuple[str, ...], ...]] = dict(
    DEFAULT_RULES,
    embed=((),), src_embed=((),), vision_embed=((),))

# Order in which dims of one tensor get to claim mesh axes (TP before FSDP
# before SP; earlier = higher priority).
PRIORITY = (
    "experts", "vocab", "heads", "mlp", "ssm_in", "ssm_inner", "ssm_heads",
    "kv_heads", "batch", "embed", "src_embed", "vision_embed", "expert_mlp",
    "ssm_conv", "kv_seq", "seq",
)


def _prio(name: str | None) -> int:
    if name in PRIORITY:
        return PRIORITY.index(name)
    return len(PRIORITY)


def spec_for_axes(axes: Sequence[str | None], shape: Sequence[int],
                  mesh: Mesh, rules: Mapping | None = None) -> P:
    """Resolve one tensor's logical axes to a PartitionSpec."""
    rules = dict(DEFAULT_RULES, **(rules or {}))
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assignment: dict[int, tuple[str, ...]] = {}
    taken: set[str] = set()
    order = sorted(range(len(axes)), key=lambda i: _prio(axes[i]))
    for i in order:
        name = axes[i]
        if name is None:
            continue
        for cand in rules.get(name, ((),)):
            cand = tuple(a for a in cand if a in mesh_sizes)
            if not cand:
                assignment[i] = ()
                break
            prod = int(np.prod([mesh_sizes[a] for a in cand]))
            if shape[i] % prod == 0 and not (set(cand) & taken):
                assignment[i] = cand
                taken |= set(cand)
                break
        else:
            assignment[i] = ()
    parts = []
    for i in range(len(axes)):
        a = assignment.get(i, ())
        parts.append(a if len(a) > 1 else (a[0] if a else None))
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(logical_tree, abstract_tree, mesh: Mesh,
                   rules: Mapping | None = None):
    """NamedSharding tree for a (logical-axes, ShapeDtypeStruct) tree pair."""

    def one(axes, aval):
        return NamedSharding(mesh, spec_for_axes(axes, aval.shape, mesh, rules))

    return jax.tree.map(one, logical_tree, abstract_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(a, (str, type(None))) for a in x))


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def data_spec(shape: Sequence[int], mesh: Mesh,
              logical: Sequence[str | None] = None) -> P:
    """Sharding for an input batch array; dim 0 is the global batch."""
    logical = logical or ("batch",) + (None,) * (len(shape) - 1)
    return spec_for_axes(logical, shape, mesh)


def cache_logical_axes(cache_tree):
    """Logical axes for a decode-cache pytree (see transformer.init_cache)."""

    def one(path, leaf):
        names = [p.key for p in path if hasattr(p, "key")]
        kind = names[-1] if names else ""
        if kind in ("k", "v", "ck", "cv"):
            return ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
        if kind == "conv":
            return ("layers", "batch", "conv", "ssm_conv")
        if kind == "ssm":
            return ("layers", "batch", "ssm_heads", "head_dim", "state")
        return tuple([None] * leaf.ndim)

    return jax.tree_util.tree_map_with_path(one, cache_tree)


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get(name, 1)
