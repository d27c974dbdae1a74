"""Pytree utilities shared across the framework.

We deliberately avoid flax/optax: parameters are plain nested dicts of
jnp arrays, and these helpers provide the small amount of structure we
need (counting, dtype casting, RNG fan-out, global-norm).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def param_count(tree) -> int:
    """Total number of scalar parameters in a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


def param_bytes(tree) -> int:
    """Total bytes of a pytree (per-leaf dtype-aware)."""
    return sum(
        int(np.prod(x.shape)) * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(tree)
    )


def cast_tree(tree, dtype):
    """Cast every floating leaf of a pytree to `dtype` (ints untouched)."""
    def _cast(x):
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(_cast, tree)


def tree_zeros_like(tree, dtype=None):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, dtype or x.dtype), tree
    )


def _sumsq(x) -> jax.Array:
    """sum(x^2) in fp32 without materializing a full f32 copy.

    XLA CPU fails to fuse convert+square+reduce on multi-GiB bf16 tensors
    (observed 3 x 5 GiB f32 temp on the 1T-param config); chunking the
    reduction over axis 0 of a stacked (3-D or more) tensor bounds the
    transient at one slice. A 2-D leaf is reduced whole: its slices are
    single rows, and a scan over millions of rows (a packed embedding
    table) is a device loop of millions of tiny steps.
    """
    if x.ndim >= 3 and x.size > (1 << 26):
        def body(acc, sl):
            return acc + jnp.sum(jnp.square(sl.astype(jnp.float32))), None
        acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), x)
        return acc
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def global_norm(tree) -> jax.Array:
    """L2 norm over every leaf of a pytree (fp32 accumulation)."""
    leaves = [_sumsq(x) for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def split_rng_tree(rng, tree):
    """One independent RNG key per leaf, packaged in the same structure."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree_util.tree_unflatten(treedef, list(keys))


def tree_map_with_path_names(fn, tree):
    """tree_map where fn also receives a '/'-joined string path."""
    def _wrap(path, leaf):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        return fn(name, leaf)
    return jax.tree_util.tree_map_with_path(_wrap, tree)
