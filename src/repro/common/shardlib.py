"""Logical-axis sharding rules (flax.partitioning-style, dependency-free).

Every model annotates each parameter / activation dimension with a *logical*
axis name ("vocab", "mlp", "batch", ...). A rules table maps logical names to
physical mesh axes. This indirection is what lets one model definition run on
a (data, model) mesh and on one device without touching model code: the
trainer takes its parameter shardings from here (`abstract_init`,
`shardings_for`) and the model code its activation constraints (`ShardCtx`).
"""
from __future__ import annotations

from typing import Mapping, Optional, Sequence, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

Axis = Union[str, Sequence[str], None]


# Default physical mapping. "fsdp" is the weight-sharding (ZeRO-3) axis;
# "batch"/"edges"/"tokens" are activation data axes. "pod" composes with
# "data" so the multi-pod mesh gets hierarchical DP for free.
DEFAULT_RULES: dict[str, Axis] = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    # Megatron-style sequence parallelism: the residual stream between
    # blocks shards its seq dim over `model` (norms/adds local; GSPMD
    # inserts the all-gather before attention/MLP and reduce-scatter after).
    # Without this, scan saves 61 full (B_loc, S, D) carries per device.
    "act_seq": "model",
    "act_embed": "model",       # residual-stream d_model sharding (alt.)
    "act_vocab": "model",
    # attention activations (q/k/v/scores); defaults fit archs whose head
    # counts divide the 16-way model axis — others override act_q_seq
    # (context parallelism) or rely on divisibility auto-drop.
    "act_q_seq": None,
    "act_kv_seq": None,
    "act_heads": "model",
    "act_kv_heads": "model",
    "act_head_dim": None,
    "edges": ("pod", "data"),
    "nodes": None,
    "candidates": ("pod", "data"),
    # batch over EVERY axis — for ops whose weight dims can't shard (e.g.
    # xDeepFM's 200 CIN filters vs the 16-way model axis)
    "act_all_batch": ("pod", "data", "model"),
    # weights
    "fsdp": ("pod", "data"),
    "embed": None,
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "expert": "model",
    "expert_mlp": None,
    "table_rows": "model",
    "table_dim": None,
    "layers": None,
    "stages": None,
    # KV cache
    "cache_batch": ("pod", "data"),
    "cache_seq": None,
    "cache_kv_heads": "model",
    "cache_head_dim": None,
}


def make_rules(overrides: Optional[Mapping[str, Axis]] = None) -> dict[str, Axis]:
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    return rules


def _filter_axis(axis: Axis, mesh_axis_names: Sequence[str]) -> Axis:
    """Drop mesh axes that don't exist on this mesh (e.g. 'pod' on 1-pod)."""
    if axis is None:
        return None
    if isinstance(axis, str):
        return axis if axis in mesh_axis_names else None
    kept = tuple(a for a in axis if a in mesh_axis_names)
    if not kept:
        return None
    return kept if len(kept) > 1 else kept[0]


def axes_prod(axis: Axis, mesh) -> int:
    if axis is None:
        return 1
    axes = (axis,) if isinstance(axis, str) else axis
    n = 1
    for a in axes:
        n *= mesh.shape.get(a, 1)
    return n


def sanitized_pspec(shape, logical, rules, mesh) -> P:
    """PartitionSpec for a concrete shape.

    Universal rule: a dim is sharded iff it divides the mesh-axis product
    (never padded, never uneven — jit in_shardings reject uneven anyway),
    and each mesh axis is used at most once per spec. Non-divisible dims
    replicate; the per-arch sharding_overrides are designed so that every
    tensor that MATTERS divides cleanly (DESIGN.md §5).
    """
    names = mesh.axis_names
    out, used = [], set()
    for dim, name in zip(shape, tuple(logical)):
        axis = None
        if name is not None:
            axis = _filter_axis(rules.get(name), names)
        if axis is not None:
            ax_tuple = (axis,) if isinstance(axis, str) else tuple(axis)
            if any(a in used for a in ax_tuple):
                axis = None
            elif dim % axes_prod(axis, mesh) != 0:
                axis = None
            else:
                used.update(ax_tuple)
        out.append(axis)
    return P(*out)


def abstract_init(init_thunk):
    """eval_shape a params initializer returning (params, logical).

    `logical` is static python data built during tracing; captured via a
    side channel because eval_shape outputs must be arrays.
    """
    side = {}

    def wrapper():
        p, lg = init_thunk()
        side["lg"] = lg
        return p

    return jax.eval_shape(wrapper), side["lg"]


def shardings_for(abstract_tree, logical_tree, rules, mesh):
    """NamedSharding tree for an abstract pytree (divisible-or-replicate,
    one `sanitized_pspec` per leaf)."""
    flat_abs, treedef = jax.tree_util.tree_flatten(abstract_tree)
    flat_lg = treedef.flatten_up_to(logical_tree)
    return jax.tree_util.tree_unflatten(treedef, [
        NamedSharding(mesh, sanitized_pspec(leaf.shape, lg, rules, mesh))
        for leaf, lg in zip(flat_abs, flat_lg)])


class ShardCtx:
    """Carries (mesh, rules) through model code for activation constraints.

    A no-arg ShardCtx() is a no-op — CPU smoke tests and pure-function unit
    tests run model code unchanged.
    """

    def __init__(self, mesh=None, rules: Optional[Mapping[str, Axis]] = None):
        self.mesh = mesh
        self.rules = dict(rules) if rules else dict(DEFAULT_RULES)

    def cs(self, x, *logical):
        if self.mesh is None:
            return x
        spec = sanitized_pspec(x.shape, logical, self.rules, self.mesh)
        return jax.lax.with_sharding_constraint(x, spec)

