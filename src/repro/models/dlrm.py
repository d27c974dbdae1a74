"""Meta DLRM (Naumov et al.) — the paper's Criteo workload.

bottom-MLP(dense) -> embedding bags (26 categorical) -> pairwise
dot-interaction -> top-MLP -> CTR logit. The 25B-parameter configuration
in the paper is dominated by the embedding tables; they are row-sharded
over the `model` mesh axis (hybrid parallelism [49] in the paper).

The dot-interaction has a Pallas kernel (kernels/dot_interact.py); this
module uses the pure-jnp form, and train/train_step.py can swap in the
kernel via cfg (the kernels' ref.py oracles are exactly these functions).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import DLRMConfig
from repro.models.embedding import multifeature_bag, tp_multifeature_bag
from repro.models.recsys import apply_mlp, bce_loss, init_mlp


def init_params(rng, cfg: DLRMConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k = jax.random.split(rng, 3)
    rows = cfg.vocab_sizes[0]
    tables = jax.random.normal(
        k[0], (cfg.n_sparse, rows, cfg.embed_dim), dtype) \
        * cfg.embed_dim ** -0.5
    bottom, bot_lg = init_mlp(
        k[1], (cfg.n_dense,) + cfg.bottom_mlp, dtype)
    n_f = cfg.n_sparse + 1                      # +1: bottom-MLP output
    n_pairs = n_f * (n_f - 1) // 2
    top_in = n_pairs + cfg.bottom_mlp[-1]
    top, top_lg = init_mlp(k[2], (top_in,) + cfg.top_mlp, dtype)
    params = {"tables": tables, "bottom": bottom, "top": top}
    logical = {"tables": (None, "table_rows", "table_dim"),
               "bottom": bot_lg, "top": top_lg}
    return params, logical


def dot_interaction(feats):
    """feats: (B, F, D) -> (B, F*(F-1)/2) lower-triangle pairwise dots."""
    b, f, d = feats.shape
    gram = jnp.einsum("bfd,bgd->bfg", feats, feats)   # (B, F, F)
    ii, jj = jnp.tril_indices(f, k=-1)
    return gram[:, ii, jj]


def forward(params, cfg: DLRMConfig, batch, *, interact_fn=None, ctx=None):
    """batch: sparse_ids (B, 26, hot), dense (B, 13) -> logits (B,).

    Each part runs under a `jax.named_scope` (`bottom_mlp`,
    `embedding_lookup`, `interaction`, `top_mlp`) that the compiled
    ops, their gradients included, carry in their metadata."""
    with jax.named_scope("bottom_mlp"):
        dense_out = apply_mlp(params["bottom"],
                              batch["dense"].astype(params["tables"].dtype),
                              final_act=True)
    with jax.named_scope("embedding_lookup"):
        if cfg.tp_lookup and ctx is not None:
            emb = tp_multifeature_bag(params["tables"], batch["sparse_ids"],
                                      ctx.mesh)
        else:
            emb = multifeature_bag(params["tables"], batch["sparse_ids"])
    with jax.named_scope("interaction"):
        feats = jnp.concatenate([dense_out[:, None, :], emb], axis=1)  # (B,27,D)
        interact = (interact_fn or dot_interaction)(feats)
    with jax.named_scope("top_mlp"):
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
