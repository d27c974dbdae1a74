"""GraphSAGE (mean aggregator) in three execution regimes.

  - full-graph: edge-list message passing via jnp.take + jax.ops.segment_sum
    (JAX's BCOO can't shard a 62M-edge SpMM; segment ops over an edge-index
    ARE the system per the assignment). Edges shard over the data axes.
  - minibatch: dense-fanout sampled blocks (B, F1, F2, d) produced by
    data/sampler.py — pure batched tensor ops, shards over batch.
  - batched small graphs: padded per-graph edge lists + vmap.

Params per layer: W_self (d_in, d_out), W_neigh (d_in, d_out), bias.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import GNNConfig


def init_params(rng, cfg: GNNConfig, d_feat: int):
    dims = [d_feat] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    dtype = jnp.dtype(cfg.param_dtype)
    layers, logical = [], []
    keys = jax.random.split(rng, cfg.n_layers)
    for l in range(cfg.n_layers):
        d_in, d_out = dims[l], dims[l + 1]
        k1, k2 = jax.random.split(keys[l])
        layers.append({
            "w_self": jax.random.normal(k1, (d_in, d_out), dtype) * d_in ** -0.5,
            "w_neigh": jax.random.normal(k2, (d_in, d_out), dtype) * d_in ** -0.5,
            "b": jnp.zeros((d_out,), dtype),
        })
        logical.append({
            "w_self": ("fsdp", None),
            "w_neigh": ("fsdp", None),
            "b": (None,),
        })
    return {"layers": tuple(layers)}, {"layers": tuple(logical)}


def _sage_combine(h_self, h_neigh, layer, *, final: bool):
    out = h_self @ layer["w_self"] + h_neigh @ layer["w_neigh"] + layer["b"]
    if not final:
        out = jax.nn.relu(out)
        # L2-normalize as in the paper (Hamilton et al. 2017, Alg. 1 line 7)
        out = out / jnp.maximum(
            jnp.linalg.norm(out, axis=-1, keepdims=True), 1e-6)
    return out


# ------------------------------------------------------------ full graph ---
def full_graph_forward(params, cfg: GNNConfig, x, edge_src, edge_dst,
                       n_nodes: int):
    """x: (N, d); edge arrays (E,) int32 (messages flow src -> dst)."""
    h = x
    n_layers = len(params["layers"])
    for l, layer in enumerate(params["layers"]):
        msg = jnp.take(h, edge_src, axis=0)                      # (E, d)
        agg = jax.ops.segment_sum(msg, edge_dst, num_segments=n_nodes)
        if cfg.aggregator == "mean":
            deg = jax.ops.segment_sum(
                jnp.ones_like(edge_dst, h.dtype), edge_dst,
                num_segments=n_nodes)
            agg = agg / jnp.maximum(deg, 1.0)[:, None]
        elif cfg.aggregator == "max":
            agg = jax.ops.segment_max(msg, edge_dst, num_segments=n_nodes)
        h = _sage_combine(h, agg, layer, final=(l == n_layers - 1))
    return h  # (N, n_classes) logits


def full_graph_loss(params, cfg, batch):
    logits = full_graph_forward(
        params, cfg, batch["x"], batch["edge_src"], batch["edge_dst"],
        batch["x"].shape[0])
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[:, None], axis=1)[:, 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, {"xent": loss}


# ------------------------------------------- dst-partitioned full graph ----
def full_graph_partitioned_loss(params, cfg: GNNConfig, batch, mesh):
    """§Perf hillclimb 3: dst-partitioned message passing via shard_map.

    Device k owns the node range [k*n_loc, (k+1)*n_loc) and every edge
    whose dst falls in it (the data pipeline buckets + pads edge shards;
    pad edges carry src = dst = -1). segment_sum lands directly in the
    local node range — the edge-sharded baseline instead psums node-sized
    PARTIALS (N x d per layer, measured 2.3 GiB/device on ogb_products).
    The only large collective left is one all_gather of the hidden state
    between layers (its transpose is the matching reduce-scatter).

    batch: x (N_pad, d) replicated; edge_src/edge_dst (n_shards, e_loc)
    int32 bucketed by dst; labels (N_pad,) sharded (-1 = masked/pad).
    """
    P = jax.sharding.PartitionSpec
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    n_pad = batch["x"].shape[0]
    assert n_pad % n_shards == 0, (n_pad, n_shards)
    n_loc = n_pad // n_shards
    row_axes = axes if len(axes) > 1 else axes[0]
    n_layers = len(params["layers"])

    def fn(p, x, src, dst, labels):
        src, dst, labels = src[0], dst[0], labels  # drop shard dim
        flat = jnp.zeros((), jnp.int32)
        for a in axes:
            flat = flat * mesh.shape[a] + jax.lax.axis_index(a)
        node0 = flat * n_loc
        ok = (src >= 0).astype(x.dtype)
        ldst = jnp.clip(dst - node0, 0, n_loc - 1)
        h_full = x
        for l, layer in enumerate(p["layers"]):
            msg = jnp.take(h_full, jnp.clip(src, 0, n_pad - 1), axis=0)
            msg = msg * ok[:, None]
            agg = jax.ops.segment_sum(msg, ldst, num_segments=n_loc)
            if cfg.aggregator == "mean":
                deg = jax.ops.segment_sum(ok, ldst, num_segments=n_loc)
                agg = agg / jnp.maximum(deg, 1.0)[:, None]
            h_self = jax.lax.dynamic_slice_in_dim(h_full, node0, n_loc)
            h_loc = _sage_combine(h_self, agg, layer,
                                  final=(l == n_layers - 1))
            if l < n_layers - 1:
                h_full = jax.lax.all_gather(h_loc, axes, axis=0, tiled=True)
        # local masked CE over this shard's label slice
        logp = jax.nn.log_softmax(h_loc.astype(jnp.float32), axis=-1)
        mask = (labels >= 0).astype(jnp.float32)
        nll = -jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[:, None], axis=1)[:, 0]
        num = jax.lax.psum(jnp.sum(nll * mask), axes)
        den = jax.lax.psum(jnp.sum(mask), axes)
        return num / jnp.maximum(den, 1.0)

    loss = jax.shard_map(
        fn, mesh=mesh, check_vma=False,
        in_specs=(jax.tree_util.tree_map(lambda _: P(), params),
                  P(None, None), P(row_axes, None), P(row_axes, None),
                  P(row_axes)),
        out_specs=P())(
        params, batch["x"], batch["edge_src"], batch["edge_dst"],
        batch["labels"])
    return loss, {"xent": loss}


# -------------------------------------------------------- sampled blocks ---
def minibatch_forward(params, cfg: GNNConfig, x0, neigh1, neigh2):
    """Dense-fanout 2-layer GraphSAGE (the assigned config is 2-layer).

    x0:     (B, d)          seed-node features
    neigh1: (B, F1, d)      1-hop neighbor features
    neigh2: (B, F1, F2, d)  2-hop neighbor features
    """
    l1, l2 = params["layers"]
    # layer 1 applied at depth-1 frontier: aggregate 2-hop into 1-hop nodes
    agg2 = jnp.mean(neigh2, axis=2)                              # (B, F1, d)
    h1 = _sage_combine(neigh1, agg2, l1, final=False)            # (B, F1, h)
    # layer 1 applied at the seeds themselves (aggregate 1-hop raw feats)
    agg1 = jnp.mean(neigh1, axis=1)                              # (B, d)
    h0 = _sage_combine(x0, agg1, l1, final=False)                # (B, h)
    # layer 2 at seeds: aggregate 1-hop hidden into seeds
    agg_h1 = jnp.mean(h1, axis=1)                                # (B, h)
    return _sage_combine(h0, agg_h1, l2, final=True)             # (B, C)


def minibatch_loss(params, cfg, batch):
    logits = minibatch_forward(params, cfg, batch["x0"], batch["neigh1"],
                               batch["neigh2"])
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return jnp.mean(nll), {"xent": jnp.mean(nll)}


# --------------------------------------------------- batched small graphs --
def batched_graphs_forward(params, cfg: GNNConfig, x, edge_src, edge_dst,
                           node_mask):
    """x: (G, N, d); edges (G, E) int32 padded (pad edges point to node 0 with
    node_mask 0); node_mask: (G, N). Returns graph-level logits (G, C) via
    masked mean pooling."""
    def single(xg, src, dst, mask):
        h = full_graph_forward(params, cfg, xg, src, dst, xg.shape[0])
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.sum(h * mask[:, None], axis=0) / denom
    return jax.vmap(single)(x, edge_src, edge_dst, node_mask)


def batched_graphs_loss(params, cfg, batch):
    logits = batched_graphs_forward(
        params, cfg, batch["x"], batch["edge_src"], batch["edge_dst"],
        batch["node_mask"])
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return jnp.mean(nll), {"xent": jnp.mean(nll)}
