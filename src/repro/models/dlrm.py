"""Meta DLRM (Naumov et al.) — the paper's Criteo workload — and
DLRM-DCNv2, the MLPerf Training recommendation model (torchrec
`DLRM_DCN`).

bottom-MLP(dense) -> embedding bags (26 categorical) -> interaction ->
top-MLP -> CTR logit. The interaction is DLRM's pairwise dot
(`interaction="dot"`) or DCNv2's low-rank cross network over the
concatenated feature vectors (`"dcn"`, `cross_net`). The 25B-parameter
configuration in the paper is dominated by the embedding tables; they
are row-sharded over the `model` mesh axis (hybrid parallelism [49] in
the paper). Tables of one size with bags of one size are stacked
(n_sparse, rows, D); tables or bags of unequal sizes are packed one
after another into one (sum(rows), D) leaf (`DLRMConfig.packed`, one
device).

The dot-interaction has a Pallas kernel (kernels/dot_interact.py); this
module uses the pure-jnp form, and train/train_step.py can swap in the
kernel via cfg (the kernels' ref.py oracles are exactly these functions).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DLRMConfig
from repro.models.embedding import (multifeature_bag, packed_multifeature_bag,
                                    tp_multifeature_bag)
from repro.models.recsys import apply_mlp, bce_loss, init_mlp


def slot_offsets(cfg: DLRMConfig) -> np.ndarray:
    """(sum(hots),): the first row, in the packed table, of the table
    that each column of the flat ids indexes."""
    first = np.concatenate([[0], np.cumsum(cfg.vocab_sizes)[:-1]])
    return np.repeat(first, cfg.hots).astype(np.int32)


def init_cross(rng, width: int, layers: int, rank: int, dtype):
    """`layers` low-rank cross layers over `width` features, as torchrec's
    `LowRankCrossNet` draws them: each kernel xavier-normal (standard
    deviation sqrt(2 / (width + rank))), each bias zero. `v` (width, rank)
    and `w` (rank, width) are torchrec's V_l and W_l transposed."""
    std = (2.0 / (width + rank)) ** 0.5
    out, logical = [], []
    for key in jax.random.split(rng, layers):
        kv, kw = jax.random.split(key)
        out.append({"v": jax.random.normal(kv, (width, rank), dtype) * std,
                    "w": jax.random.normal(kw, (rank, width), dtype) * std,
                    "b": jnp.zeros((width,), dtype)})
        logical.append({"v": (None, None), "w": (None, None), "b": (None,)})
    return tuple(out), tuple(logical)


def init_params(rng, cfg: DLRMConfig):
    if cfg.interaction not in ("dot", "dcn"):
        raise ValueError(f"unknown interaction {cfg.interaction!r}")
    dtype = jnp.dtype(cfg.param_dtype)
    k = jax.random.split(rng, 3)
    if cfg.packed:
        tables = jax.random.normal(
            k[0], (sum(cfg.vocab_sizes), cfg.embed_dim), dtype) \
            * cfg.embed_dim ** -0.5
        tables_lg = ("table_rows", "table_dim")
    else:
        rows = cfg.vocab_sizes[0]
        tables = jax.random.normal(
            k[0], (cfg.n_sparse, rows, cfg.embed_dim), dtype) \
            * cfg.embed_dim ** -0.5
        tables_lg = (None, "table_rows", "table_dim")
    bottom, bot_lg = init_mlp(
        k[1], (cfg.n_dense,) + cfg.bottom_mlp, dtype)
    n_f = cfg.n_sparse + 1                      # +1: bottom-MLP output
    params = {"tables": tables, "bottom": bottom}
    logical = {"tables": tables_lg, "bottom": bot_lg}
    if cfg.interaction == "dcn":
        top_in = n_f * cfg.embed_dim
        params["cross"], logical["cross"] = init_cross(
            jax.random.fold_in(k[2], 1), top_in, cfg.dcn_layers,
            cfg.dcn_rank, dtype)
    else:
        top_in = n_f * (n_f - 1) // 2 + cfg.bottom_mlp[-1]
    params["top"], logical["top"] = init_mlp(
        k[2], (top_in,) + cfg.top_mlp, dtype)
    return params, logical


def dot_interaction(feats):
    """feats: (B, F, D) -> (B, F*(F-1)/2) lower-triangle pairwise dots."""
    b, f, d = feats.shape
    gram = jnp.einsum("bfd,bgd->bfg", feats, feats)   # (B, F, F)
    ii, jj = jnp.tril_indices(f, k=-1)
    return gram[:, ii, jj]


def cross_net(layers, x0):
    """torchrec `LowRankCrossNet` over x0 (B, width): for each layer l,
    x_{l+1} = x_0 * (W_l (V_l x_l) + b_l) + x_l, with `v` = V_l^T and
    `w` = W_l^T, so V_l x_l is x_l @ v; returns the last x."""
    x = x0
    for layer in layers:
        x = x0 * ((x @ layer["v"]) @ layer["w"] + layer["b"]) + x
    return x


def forward(params, cfg: DLRMConfig, batch, *, interact_fn=None, ctx=None):
    """batch: sparse_ids (B, 26, hot), or (B, sum(hots)) for packed
    tables, dense (B, 13) -> logits (B,).

    Each part runs under a `jax.named_scope` (`bottom_mlp`,
    `embedding_lookup`, with `bag_lookup` inside it for packed tables,
    `interaction`, with `cross_net` inside it for "dcn", `top_mlp`) that
    the compiled ops, their gradients included, carry in their
    metadata."""
    with jax.named_scope("bottom_mlp"):
        dense_out = apply_mlp(params["bottom"],
                              batch["dense"].astype(params["tables"].dtype),
                              final_act=True)
    with jax.named_scope("embedding_lookup"):
        if cfg.packed:
            if ctx is not None:
                raise NotImplementedError(
                    "packed tables (unequal table or bag sizes) run on one "
                    "device; no mesh lookup exists for them")
            emb = packed_multifeature_bag(params["tables"], batch["sparse_ids"],
                                          slot_offsets(cfg), cfg.hots)
        elif cfg.tp_lookup and ctx is not None:
            emb = tp_multifeature_bag(params["tables"], batch["sparse_ids"],
                                      ctx.mesh)
        else:
            emb = multifeature_bag(params["tables"], batch["sparse_ids"])
    with jax.named_scope("interaction"):
        feats = jnp.concatenate([dense_out[:, None, :], emb], axis=1)  # (B,27,D)
        if cfg.interaction == "dcn":
            with jax.named_scope("cross_net"):
                crossed = cross_net(params["cross"],
                                    feats.reshape(feats.shape[0], -1))
        else:
            interact = (interact_fn or dot_interaction)(feats)
    with jax.named_scope("top_mlp"):
        if cfg.interaction == "dcn":
            top_in = crossed
        else:
            top_in = jnp.concatenate([interact, dense_out], axis=-1)
        return apply_mlp(params["top"], top_in)[:, 0]


def loss_fn(params, cfg: DLRMConfig, batch, *, interact_fn=None, ctx=None):
    logit = forward(params, cfg, batch, interact_fn=interact_fn, ctx=ctx)
    loss = bce_loss(logit, batch["label"].astype(jnp.float32))
    return loss, {"bce": loss}


def score_candidates(params, cfg: DLRMConfig, user, cand_ids, *,
                     chunks: int = 25, ctx=None):
    """Retrieval scoring with the user side computed ONCE.

    Naively calling forward() per candidate chunk re-gathers the 25 user
    features x C rows from the sharded tables every chunk (measured
    13.6 GiB/device of collective traffic); only feature 0 (the item)
    actually varies, so we look up the user features once and gather just
    the candidate column per chunk.
    """
    dense_out = apply_mlp(params["bottom"],
                          user["dense"].astype(params["tables"].dtype),
                          final_act=True)                     # (1, D)
    user_emb = multifeature_bag(params["tables"], user["sparse_ids"])
    c = cand_ids.shape[0]
    assert c % chunks == 0

    def score_chunk(ids):
        if ctx is not None:
            ids = ctx.cs(ids, "candidates")
        cc = ids.shape[0]
        item_emb = jnp.take(params["tables"][0],
                            ids % cfg.vocab_sizes[0], axis=0)  # (cc, D)
        feats = jnp.concatenate([
            jnp.broadcast_to(dense_out, (cc, dense_out.shape[-1]))[:, None],
            item_emb[:, None],
            jnp.broadcast_to(user_emb[0, 1:][None],
                             (cc, cfg.n_sparse - 1, cfg.embed_dim)),
        ], axis=1)                                             # (cc, 27, D)
        interact = dot_interaction(feats)
        top_in = jnp.concatenate(
            [interact, jnp.broadcast_to(dense_out,
                                        (cc, dense_out.shape[-1]))], -1)
        return apply_mlp(params["top"], top_in)[:, 0]

    blocks = cand_ids.reshape(chunks, c // chunks)
    if ctx is not None:
        blocks = ctx.cs(blocks, None, "candidates")
    out = jax.lax.map(score_chunk, blocks)
    return out.reshape(c)
