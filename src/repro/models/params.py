"""Parameter descriptor system.

Every model defines a single ``param_descs(cfg)`` tree whose leaves are
:class:`PDesc` (shape + logical axis names + init kind). From that one
source of truth we derive: real initialization (tests), allocation-free
abstract params (dry-run), and PartitionSpecs (pjit), guaranteeing the
three can never drift apart structurally.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec


@dataclass(frozen=True)
class PDesc:
    """Parameter leaf descriptor: shape, logical axes, init style."""
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones | small
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_desc(x) -> bool:
    return isinstance(x, PDesc)


def stack(desc: PDesc, n: int, axis_name: Optional[str] = "layers") -> PDesc:
    """Prepend a stacked-layer dimension (scanned over; never sharded)."""
    return PDesc((n,) + desc.shape, (axis_name,) + desc.axes, desc.init, desc.scale)


def stack_tree(tree, n: int):
    return jax.tree_util.tree_map(lambda d: stack(d, n), tree, is_leaf=is_desc)


# --------------------------------------------------------------------------- #
# materialization                                                              #
# --------------------------------------------------------------------------- #
_HEAD_AXES = ("heads", "kv_heads")


def fan_in(desc: PDesc) -> int:
    """The size of the dimensions a weight contracts with its input: the
    second-to-last dimension of a matrix; of an attention projection, the
    input width of ``(in, heads, head_dim)`` and ``heads * head_dim`` of
    ``(heads, head_dim, out)``."""
    shape, axes = desc.shape, desc.axes
    if len(shape) < 2:
        return shape[-1]
    if len(shape) >= 3 and axes[-2] in _HEAD_AXES:
        return shape[-3]
    if len(shape) >= 3 and axes[-3] in _HEAD_AXES:
        return shape[-3] * shape[-2]
    return shape[-2]


def _init_leaf(desc: PDesc, key: jax.Array, dtype) -> jax.Array:
    if desc.init == "zeros":
        return jnp.zeros(desc.shape, dtype)
    if desc.init == "ones":
        return jnp.ones(desc.shape, dtype)
    std = desc.scale / np.sqrt(max(fan_in(desc), 1))
    if desc.init == "small":
        std = 0.01 * desc.scale
    return (jax.random.normal(key, desc.shape, jnp.float32) * std).astype(dtype)


def init_params(descs, key: jax.Array, dtype=jnp.bfloat16):
    leaves, treedef = jax.tree_util.tree_flatten(descs, is_leaf=is_desc)
    keys = jax.random.split(key, len(leaves))
    arrays = [_init_leaf(d, k, dtype) for d, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, arrays)


def abstract_params(descs, dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree — no allocation (dry-run path)."""
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype), descs, is_leaf=is_desc
    )


# --------------------------------------------------------------------------- #
# sharding resolution                                                          #
# --------------------------------------------------------------------------- #
#: logical axes earlier in this list claim mesh axes first (e.g. kv_heads
#: beats the seq fallback for decode caches; experts beats expert_ffn).
_PRIORITY = {
    "vocab": 0, "heads": 0, "kv_heads": 0, "ffn": 0, "experts": 0,
    "batch": 1, "embed": 2, "expert_ffn": 2, "seq": 3,
}


def resolve_spec(
    desc: PDesc,
    rules: Mapping[str, Tuple[str, ...]],
    mesh_axis_sizes: Mapping[str, int],
) -> PartitionSpec:
    """Logical axes -> PartitionSpec. Assignments that do not divide the
    dimension or that reuse a consumed mesh axis are dropped; contested mesh
    axes go to the highest-priority logical axis (fallback chains)."""
    used: set = set()
    out: list = [None] * len(desc.shape)
    order = sorted(
        range(len(desc.shape)),
        key=lambda i: _PRIORITY.get(desc.axes[i], 9) if desc.axes[i] else 99,
    )
    for i in order:
        dim, logical = desc.shape[i], desc.axes[i]
        if logical is None or logical not in rules:
            continue
        mesh_axes = rules[logical]
        mesh_axes = (mesh_axes,) if isinstance(mesh_axes, str) else tuple(mesh_axes)
        mesh_axes = tuple(a for a in mesh_axes if a not in used)
        total = 1
        for a in mesh_axes:
            total *= mesh_axis_sizes.get(a, 1)
        if mesh_axes and total > 1 and dim % total == 0:
            out[i] = mesh_axes if len(mesh_axes) > 1 else mesh_axes[0]
            used.update(mesh_axes)
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def resolve_specs(descs, rules, mesh_axis_sizes):
    return jax.tree_util.tree_map(
        lambda d: resolve_spec(d, rules, mesh_axis_sizes), descs, is_leaf=is_desc
    )


def param_count(descs) -> int:
    leaves = jax.tree_util.tree_leaves(descs, is_leaf=is_desc)
    return int(sum(int(np.prod(d.shape)) for d in leaves))


def param_bytes(descs, bytes_per_param: int = 2) -> int:
    return param_count(descs) * bytes_per_param
