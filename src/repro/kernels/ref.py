"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax.numpy as jnp


def embedding_bag_ref(table, ids, *, combiner: str = "sum"):
    """table: (V, D); ids: (B, bag) -> (B, D)."""
    emb = jnp.take(table, ids, axis=0)          # (B, bag, D)
    out = jnp.sum(emb, axis=1)
    if combiner == "mean":
        out = out / ids.shape[1]
    return out


def dot_interact_ref(feats):
    """feats: (B, F, D) -> (B, F*(F-1)/2) lower-triangle pairwise dots."""
    f = feats.shape[1]
    gram = jnp.einsum("bfd,bgd->bfg", feats, feats,
                      preferred_element_type=jnp.float32)
    ii, jj = jnp.tril_indices(f, k=-1)
    return gram[:, ii, jj].astype(feats.dtype)


def sage_aggregate_ref(neigh, w):
    """neigh: (B, F, D); w: (D, H) -> mean over F then project: (B, H)."""
    agg = jnp.mean(neigh.astype(jnp.float32), axis=1)
    return (agg @ w.astype(jnp.float32)).astype(neigh.dtype)
