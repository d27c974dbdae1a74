"""Per-architecture smoke tests: REDUCED config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs (assignment
requirement). No test or benchmark cell runs the FULL configs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (DLRMConfig, GNNConfig, RecsysConfig,
                                TransformerConfig)
from repro.models import dlrm as dlrm_lib
from repro.models import gnn as gnn_lib
from repro.models import recsys as recsys_lib
from repro.models import transformer as tfm
from repro.train.optim import make_optimizer
from repro.train.train_step import make_train_step

RNG = jax.random.PRNGKey(0)


def _finite(tree):
    return all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree_util.tree_leaves(tree)
               if jnp.issubdtype(x.dtype, jnp.floating))


# ------------------------------------------------------------ LM family ----
LM_REDUCED = {
    "qwen2-moe-a2.7b": TransformerConfig(
        name="qwen2-moe-r", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=96, vocab_size=512, n_experts=8,
        n_shared_experts=2, top_k=2, d_expert=48, qkv_bias=True,
        tie_embeddings=False, param_dtype="float32", attn_chunk=32),
    "kimi-k2-1t-a32b": TransformerConfig(
        name="kimi-r", n_layers=2, d_model=64, n_heads=8, n_kv_heads=2,
        head_dim=14, d_ff=48, vocab_size=512, n_experts=16,
        n_shared_experts=1, top_k=4, d_expert=48, tie_embeddings=False,
        param_dtype="float32", attn_chunk=32),
    "smollm-135m": TransformerConfig(
        name="smollm-r", n_layers=3, d_model=48, n_heads=3, n_kv_heads=1,
        head_dim=16, d_ff=128, vocab_size=512, tie_embeddings=True,
        param_dtype="float32", attn_chunk=32),
    "gemma2-2b": TransformerConfig(
        name="gemma2-r", n_layers=4, d_model=48, n_heads=2, n_kv_heads=1,
        head_dim=24, d_ff=96, vocab_size=512, sliding_window=16,
        local_global_alternating=True, attn_logit_softcap=50.0,
        final_logit_softcap=30.0, post_norm=True, scale_embed=True,
        act="gelu", scan_block=2, param_dtype="float32", attn_chunk=32),
    "qwen2.5-32b": TransformerConfig(
        name="qwen25-r", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=160, vocab_size=512, qkv_bias=True,
        tie_embeddings=False, param_dtype="float32", attn_chunk=32),
}


@pytest.mark.parametrize("arch_id", sorted(LM_REDUCED))
def test_lm_train_step(arch_id):
    cfg = LM_REDUCED[arch_id]
    params, _ = tfm.init_params(RNG, cfg)
    opt = make_optimizer("adam", lr=1e-3)
    opt_state = opt.init(params)
    step = make_train_step(lambda p, b: tfm.loss_fn(p, cfg, b), opt)
    toks = jax.random.randint(RNG, (4, 64), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    params, opt_state, metrics = jax.jit(step)(params, opt_state, 0, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert _finite(params)


@pytest.mark.parametrize("arch_id", ["gemma2-2b", "qwen2.5-32b"])
def test_lm_decode_step(arch_id):
    cfg = LM_REDUCED[arch_id]
    params, _ = tfm.init_params(RNG, cfg)
    cache = tfm.init_cache(cfg, 2, 32, dtype=jnp.float32)
    toks = jax.random.randint(RNG, (2,), 0, cfg.vocab_size)
    logits, cache = tfm.decode_step(params, cfg, cache, toks, 0)
    assert logits.shape == (2, cfg.vocab_size)
    assert _finite(logits)


# ----------------------------------------------------------------- GNN -----
def test_graphsage_smoke():
    cfg = GNNConfig(name="sage-r", n_layers=2, d_hidden=16, n_classes=5)
    params, _ = gnn_lib.init_params(RNG, cfg, d_feat=12)
    n, e = 50, 200
    batch = {
        "x": jax.random.normal(RNG, (n, 12)),
        "edge_src": jax.random.randint(RNG, (e,), 0, n),
        "edge_dst": jax.random.randint(RNG, (e,), 0, n),
        "labels": jax.random.randint(RNG, (n,), 0, 5),
    }
    opt = make_optimizer("adam", lr=1e-3)
    step = make_train_step(
        lambda p, b: gnn_lib.full_graph_loss(p, cfg, b), opt)
    params2, _, metrics = jax.jit(step)(params, opt.init(params), 0, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert _finite(params2)
    # minibatch + molecule regimes
    mb = {"x0": jax.random.normal(RNG, (8, 12)),
          "neigh1": jax.random.normal(RNG, (8, 5, 12)),
          "neigh2": jax.random.normal(RNG, (8, 5, 3, 12)),
          "labels": jax.random.randint(RNG, (8,), 0, 5)}
    loss, _ = gnn_lib.minibatch_loss(params, cfg, mb)
    assert np.isfinite(float(loss))
    bg = {"x": jax.random.normal(RNG, (4, 30, 12)),
          "edge_src": jax.random.randint(RNG, (4, 64), 0, 30),
          "edge_dst": jax.random.randint(RNG, (4, 64), 0, 30),
          "node_mask": jnp.ones((4, 30)),
          "labels": jax.random.randint(RNG, (4,), 0, 5)}
    loss, _ = gnn_lib.batched_graphs_loss(params, cfg, bg)
    assert np.isfinite(float(loss))


# -------------------------------------------------------------- recsys -----
RECSYS_REDUCED = {
    "wide-deep": RecsysConfig(
        name="wide-deep", interaction="concat", n_sparse=6, embed_dim=8,
        mlp_dims=(32, 16), n_dense=4, vocab_sizes=(256,) * 6, multi_hot=2),
    "xdeepfm": RecsysConfig(
        name="xdeepfm", interaction="cin", n_sparse=6, embed_dim=8,
        mlp_dims=(32, 16), n_dense=4, vocab_sizes=(256,) * 6,
        cin_dims=(12, 12, 12)),
    "dien": RecsysConfig(
        name="dien", interaction="augru", embed_dim=8, seq_len=12,
        gru_dim=16, mlp_dims=(32, 16), n_dense=4, vocab_sizes=(256,)),
    "bert4rec": RecsysConfig(
        name="bert4rec", interaction="bidir-seq", embed_dim=16, n_blocks=2,
        n_heads=2, seq_len=12, n_items=256, vocab_sizes=(256,),
        n_mask=3, n_negatives=7),
}


def _recsys_batch(cfg, b=16):
    r = np.random.RandomState(0)
    if cfg.name in ("wide-deep", "xdeepfm"):
        return {"sparse_ids": jnp.asarray(
                    r.randint(0, 256, (b, cfg.n_sparse, cfg.multi_hot)),
                    jnp.int32),
                "dense": jnp.asarray(r.randn(b, cfg.n_dense), jnp.float32),
                "label": jnp.asarray(r.rand(b) < 0.5, jnp.float32)}
    if cfg.name == "dien":
        return {"hist_ids": jnp.asarray(
                    r.randint(0, 256, (b, cfg.seq_len)), jnp.int32),
                "hist_mask": jnp.ones((b, cfg.seq_len), jnp.float32),
                "target_id": jnp.asarray(r.randint(0, 256, b), jnp.int32),
                "dense": jnp.asarray(r.randn(b, cfg.n_dense), jnp.float32),
                "label": jnp.asarray(r.rand(b) < 0.5, jnp.float32)}
    from repro.data.synthetic import bert4rec_batch
    return {k: jnp.asarray(v) for k, v in bert4rec_batch(
        r, b, cfg.seq_len, cfg.n_items, cfg.n_mask, cfg.n_negatives).items()}


@pytest.mark.parametrize("arch_id", sorted(RECSYS_REDUCED))
def test_recsys_train_step(arch_id):
    cfg = RECSYS_REDUCED[arch_id]
    params, _ = recsys_lib.INIT[cfg.name](RNG, cfg)
    if cfg.name == "bert4rec":
        loss_fn = lambda p, b: recsys_lib.bert4rec_loss(p, cfg, b)
    else:
        fwd = recsys_lib.FORWARD[cfg.name]
        loss_fn = lambda p, b: recsys_lib.ctr_loss(p, cfg, b, fwd)
    opt = make_optimizer("adagrad", lr=1e-2)
    step = make_train_step(loss_fn, opt)
    batch = _recsys_batch(cfg)
    params2, _, metrics = jax.jit(step)(params, opt.init(params), 0, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert _finite(params2)


@pytest.mark.parametrize("arch_id", sorted(RECSYS_REDUCED))
def test_recsys_retrieval(arch_id):
    cfg = RECSYS_REDUCED[arch_id]
    params, _ = recsys_lib.INIT[cfg.name](RNG, cfg)
    batch = _recsys_batch(cfg, b=1)
    user = {k: v for k, v in batch.items()
            if k not in ("label", "mask_pos", "mask_labels", "neg_ids")}
    cand = jnp.arange(50, dtype=jnp.int32)
    scores = recsys_lib.score_candidates(params, cfg, user, cand)
    assert scores.shape == (50,)
    assert _finite(scores)
    # chunked == unchunked
    chunked = recsys_lib.score_candidates(params, cfg, user, cand, chunks=5)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(chunked),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------- DLRM -----
def test_dlrm_smoke():
    cfg = DLRMConfig(name="dlrm-r", n_sparse=6, n_dense=4, embed_dim=8,
                     vocab_sizes=(256,) * 6, bottom_mlp=(16, 8),
                     top_mlp=(32, 16, 1))
    params, _ = dlrm_lib.init_params(RNG, cfg)
    r = np.random.RandomState(0)
    batch = {"sparse_ids": jnp.asarray(r.randint(0, 256, (16, 6, 1)),
                                       jnp.int32),
             "dense": jnp.asarray(r.randn(16, 4), jnp.float32),
             "label": jnp.asarray(r.rand(16) < 0.3, jnp.float32)}
    opt = make_optimizer("adagrad", lr=1e-2)
    step = make_train_step(lambda p, b: dlrm_lib.loss_fn(p, cfg, b), opt)
    params2, _, metrics = jax.jit(step)(params, opt.init(params), 0, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert _finite(params2)
