"""Integration: real training loops decrease loss (DLRM on the synthetic
Criteo stream through the actual pipeline UDFs; tiny LM on a token stream;
grad-compression allreduce equivalence)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DLRMConfig, TransformerConfig
from repro.data.synthetic import CriteoStream, TokenStream
from repro.models import dlrm as dlrm_lib
from repro.models import transformer as tfm
from repro.train.optim import make_optimizer
from repro.train.train_step import make_train_step


def test_dlrm_loss_decreases():
    cfg = DLRMConfig(name="dlrm-int", n_sparse=8, n_dense=6, embed_dim=16,
                     vocab_sizes=(4096,) * 8, bottom_mlp=(32, 16),
                     top_mlp=(64, 32, 1))
    stream = CriteoStream(n_sparse=8, n_dense=6, vocab=4096, seed=0)
    params, _ = dlrm_lib.init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adagrad", lr=0.05)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(
        lambda p, b: dlrm_lib.loss_fn(p, cfg, b), opt))
    losses = []
    for i in range(60):
        # run the REAL online UDF path: raw block -> feature_udf -> batch
        batch = stream.feature_udf(stream.raw_block(256))
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, metrics = step(params, opt_state, i, batch)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) * 0.98
    assert np.isfinite(losses).all()


def test_lm_loss_decreases_with_microbatching():
    cfg = TransformerConfig(
        name="lm-int", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=64, vocab_size=256, param_dtype="float32",
        attn_chunk=16, remat="full")
    stream = TokenStream(256, 32, seed=0)
    params, _ = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("adam", lr=3e-3)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(
        lambda p, b: tfm.loss_fn(p, cfg, b), opt, microbatches=2))
    losses = []
    for i in range(40):
        batch = {k: jnp.asarray(v) for k, v in stream.batch(16).items()}
        params, opt_state, metrics = step(params, opt_state, i, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_microbatched_grads_match_full_batch():
    cfg = TransformerConfig(
        name="lm-mb", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2,
        head_dim=8, d_ff=32, vocab_size=64, param_dtype="float32",
        attn_chunk=8, remat="none")
    params, _ = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = make_optimizer("sgd", lr=0.1, momentum=0.0)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                          0, 64)}
    batch["labels"] = batch["tokens"]
    loss_fn = lambda p, b: tfm.loss_fn(p, cfg, b)
    s1 = make_train_step(loss_fn, opt, microbatches=1)
    s2 = make_train_step(loss_fn, opt, microbatches=4)
    p1, _, _ = jax.jit(s1)(params, opt.init(params), 0, batch)
    p2, _, _ = jax.jit(s2)(params, opt.init(params), 0, batch)
    for a, b_ in zip(jax.tree_util.tree_leaves(p1),
                     jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-4)


def test_grad_compression_psum():
    """bf16/int8 compressed allreduce ~= exact mean (shard_map, 1 device)."""
    from repro.train.collectives import psum_tree
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    g = {"w": jnp.asarray(np.random.RandomState(0).randn(64), jnp.float32)}

    for mode, tol in [("none", 1e-7), ("bf16", 1e-2), ("int8", 2e-2)]:
        out = jax.jit(jax.shard_map(
            lambda t: psum_tree(t, ("data",), compress=mode),
            mesh=mesh, in_specs=({"w": P()},), out_specs={"w": P()},
            check_vma=False))(g)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(g["w"]), rtol=tol, atol=tol)
