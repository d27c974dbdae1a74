"""DLRM-DCNv2 on the program's one path: tables of unequal rows packed
into one leaf, fixed bags of unequal sizes, a low-rank cross network.

Compared on the CPU, at a small size and on seeded random weights, with
a plain float32 reference written here apart from the program: one
(rows, D) array per table, each bag summed id by id, the cross layers as
torchrec's `LowRankCrossNet` equation, the row-wise Adagrad update as
its formula. The CPU computes float32 products in float32, so the two
differ only by the order of float32 sums: the tolerances below are a few
ulps of float32 (eps 1.19e-7) grown over the sums they cross.
"""
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DLRMConfig
from repro.data.featurize import RecordSpec, featurize_block, hash_ids, raw_block
from repro.data.simulator import MachineSpec
from repro.models import dlrm
from repro.models.embedding import embedding_bag, packed_multifeature_bag
from repro.train.feed_loop import DLRMTrainer, criteo_record, train_on_feed
from repro.train.optim import make_optimizer
from repro.train.train_step import make_train_step

ROWS = (50, 7, 300, 3)
HOTS = (3, 1, 5, 2)
CFG = DLRMConfig(name="dcnv2-tiny", n_sparse=4, n_dense=5, embed_dim=8,
                 vocab_sizes=ROWS, hot_sizes=HOTS, bottom_mlp=(16, 8),
                 top_mlp=(32, 16, 1), interaction="dcn", dcn_layers=2,
                 dcn_rank=4)
# products and sums of float32 over at most a few hundred terms
RTOL, ATOL = 2e-5, 1e-6


def _batch(cfg: DLRMConfig, b: int, seed: int):
    rng = np.random.RandomState(seed)
    cols = [rng.randint(0, v, (b, h)) for v, h in zip(cfg.vocab_sizes, cfg.hots)]
    return {"sparse_ids": np.concatenate(cols, 1).astype(np.int32),
            "dense": rng.randn(b, cfg.n_dense).astype(np.float32),
            "label": (rng.rand(b) < 0.3).astype(np.float32)}


def _split(tables, rows):
    return list(jnp.split(tables, np.cumsum(rows)[:-1]))


def _ref_loss(params, tables, batch, cfg: DLRMConfig):
    """The plain reference: `tables` a list of (rows_f, D) arrays."""
    x = batch["dense"]
    for layer in params["bottom"]:
        x = jax.nn.relu(x @ layer["w"] + layer["b"])
    feats, col = [x], 0
    for table, h in zip(tables, cfg.hots):
        bag = jnp.zeros_like(x)
        for j in range(h):
            bag = bag + table[batch["sparse_ids"][:, col + j]]
        feats.append(bag)
        col += h
    x0 = jnp.concatenate(feats, axis=1)
    xl = x0
    for c in params["cross"]:          # x_{l+1} = x_0 * (W (V x_l) + b) + x_l
        xl = x0 * ((xl @ c["v"]) @ c["w"] + c["b"]) + xl
    z = xl
    for n, layer in enumerate(params["top"]):
        z = z @ layer["w"] + layer["b"]
        if n < len(params["top"]) - 1:
            z = jax.nn.relu(z)
    z = z[:, 0]
    y = batch["label"]
    loss = jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))
    return loss, z


def _assert_tree_close(got, want):
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=RTOL,
                                   atol=ATOL, err_msg=jax.tree_util.keystr(path))


def test_dcn_logits_loss_and_gradients_follow_the_reference():
    params, _ = dlrm.init_params(jax.random.PRNGKey(3), CFG)
    assert params["tables"].shape == (sum(ROWS), 8)
    assert [c["v"].shape for c in params["cross"]] == [(40, 4)] * 2
    assert params["top"][0]["w"].shape == (40, 32)
    batch = {k: jnp.asarray(v) for k, v in _batch(CFG, 64, 0).items()}
    with jax.default_matmul_precision("highest"):
        logits = dlrm.forward(params, CFG, batch)
        (loss, _), grads = jax.value_and_grad(dlrm.loss_fn, has_aux=True)(
            params, CFG, batch)

        def ref(p):
            rest = {k: v for k, v in p.items() if k != "tables"}
            return _ref_loss(rest, _split(p["tables"], ROWS), batch, CFG)
        (ref_loss, ref_logits), ref_grads = jax.value_and_grad(ref, has_aux=True)(params)
    np.testing.assert_allclose(logits, ref_logits, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(loss, ref_loss, rtol=RTOL)
    _assert_tree_close(grads, ref_grads)
    # every table is looked up and so gets a gradient
    for g in _split(grads["tables"], ROWS):
        assert float(jnp.abs(g).sum()) > 0


def test_dcn_rowwise_adagrad_step_follows_the_reference():
    """One train step: the packed table row-wise (one accumulator a
    row), every other leaf element-wise."""
    params, _ = dlrm.init_params(jax.random.PRNGKey(4), CFG)
    opt = make_optimizer("rowwise_adagrad", lr=0.02, rowwise_min_elems=2048)
    state = opt.init(params)
    assert state["acc"]["tables"].shape == (sum(ROWS),)
    batch = {k: jnp.asarray(v) for k, v in _batch(CFG, 64, 1).items()}
    step = make_train_step(lambda p, b: dlrm.loss_fn(p, CFG, b), opt)
    with jax.default_matmul_precision("highest"):
        new, new_state, _ = jax.jit(step)(params, state, 50, batch)
        grads = jax.grad(lambda p: dlrm.loss_fn(p, CFG, batch)[0])(params)
    lr = 0.02 * 50 / 100                     # linear warm-up over 100 steps
    acc = jnp.mean(jnp.square(grads["tables"]), axis=-1)
    want = {"tables": params["tables"] - lr * grads["tables"]
            * jax.lax.rsqrt(acc + 1e-10)[:, None]}
    for k in ("bottom", "cross", "top"):
        want[k] = jax.tree_util.tree_map(
            lambda p, g: p - lr * g * jax.lax.rsqrt(jnp.square(g) + 1e-10),
            params[k], grads[k])
    _assert_tree_close(new, want)
    np.testing.assert_allclose(new_state["acc"]["tables"], acc, rtol=RTOL, atol=ATOL)


def _cell(key):
    """A key of the DCNv2 benchmark configuration."""
    path = (pathlib.Path(__file__).resolve().parents[1]
            / "benchmarks/chip/configs/dlrm-dcnv2-1chip.json")
    return json.loads(path.read_text())[key]


# the cell's own 26 bags (214 ids: 214 % 8 == 6) over tables of at most 97 rows
CELL_HOTS = tuple(_cell("hot_sizes"))
CELL_ROWS = tuple(min(r, 97) for r in _cell("rows"))
# slot axes of 11, 5, 6, 6 and 214 ids are padded; 8, 16 and 24 are whole tiles
BAGS = [(ROWS, HOTS), ((10, 10), (1, 4)), ((5,), (6,)), ((9, 4, 2), (2, 2, 2)),
        ((9, 4, 2), (2, 2, 4)), ((10, 10), (3, 13)), ((7, 30, 2), (8, 8, 8)),
        (CELL_ROWS, CELL_HOTS)]


def _slice_bags(table, ids, slot_offsets, hots):
    """The bags summed slice by slice: one gather, a sum over each bag's
    columns, the sums stacked."""
    rows = jnp.take(table, ids + jnp.asarray(slot_offsets), axis=0)
    out, start = [], 0
    for h in hots:
        out.append(jnp.sum(rows[:, start:start + h], axis=1))
        start += h
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("rows,hots", BAGS)
def test_packed_bag_equals_per_table_bags(rows, hots):
    cfg = CFG.replace(n_sparse=len(rows), vocab_sizes=rows, hot_sizes=hots)
    table = jax.random.normal(jax.random.PRNGKey(0), (sum(rows), 8))
    ids = _batch(cfg, 16, 2)["sparse_ids"]
    got = packed_multifeature_bag(table, jnp.asarray(ids), dlrm.slot_offsets(cfg), hots)
    assert got.shape == (16, len(rows), 8)
    ends = np.cumsum(hots)
    # the two sum a bag's N(0, 1) rows in different orders: a few float32
    # ulps for bags of up to 8 ids, growing with the bag beyond that
    tol = 1e-6 * max(1, max(hots) / 8)
    for f, (t, e, h) in enumerate(zip(_split(table, rows), ends, hots)):
        np.testing.assert_allclose(got[:, f], embedding_bag(t, ids[:, e - h:e]),
                                   rtol=1e-6, atol=tol)


@pytest.mark.parametrize("rows,hots", BAGS)
def test_packed_bag_gradient_equals_the_slice_sums(rows, hots):
    """The whole table's gradient of a weighted sum of the bags is the
    slice-by-slice formulation's; the pad columns' rows get nothing."""
    cfg = CFG.replace(n_sparse=len(rows), vocab_sizes=rows, hot_sizes=hots)
    table = jax.random.normal(jax.random.PRNGKey(1), (sum(rows), 8))
    ids = jnp.asarray(_batch(cfg, 48, 3)["sparse_ids"])
    offsets = dlrm.slot_offsets(cfg)
    w = jax.random.normal(jax.random.PRNGKey(2), (48, len(rows), 8))

    def grad(bags):
        return jax.grad(lambda t: jnp.sum(w * bags(t, ids, offsets, hots)))(table)
    got, want = grad(packed_multifeature_bag), grad(_slice_bags)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    untouched = np.setdiff1d(np.arange(sum(rows)), np.asarray(ids + offsets))
    assert not np.asarray(got)[untouched].any()


@pytest.mark.parametrize("vocabs,hots", [(ROWS, HOTS), ((3, 1 << 19, 63), (1, 100, 27))])
def test_featurized_bags_hold_their_size_and_lie_in_their_tables(vocabs, hots):
    rs = RecordSpec(batch=128, n_sparse=len(vocabs), n_dense=13, vocabs=vocabs,
                    hots=hots, seed=5)
    raw = raw_block(np.random.RandomState(0), rs)
    assert raw["raw_ids"].shape == (128, sum(hots))       # exactly each bag size
    assert "lengths" not in raw
    out = featurize_block(raw, rs)
    ids = out["sparse_ids"]
    assert ids.shape == (128, sum(hots)) and ids.dtype == np.int32
    col = 0
    for v, h in zip(vocabs, hots):
        bag = ids[:, col:col + h]
        assert bag.min() >= 0 and bag.max() < v
        np.testing.assert_array_equal(bag, hash_ids(raw["raw_ids"][:, col:col + h], v))
        col += h
    assert out["dense"].shape == (128, 13) and out["label"].shape == (128,)


@pytest.mark.parametrize("shape,loops", [((1 << 20, 128), False),    # a packed table
                                          ((8, 1 << 17, 128), True)])  # stacked tables
def test_global_norm_scans_only_stacked_leaves(shape, loops):
    """The optimizer's global norm reduces a large 2-D leaf in one pass;
    only a stacked leaf is scanned, slice by slice (a scan over a packed
    table's rows would be a device loop of a million steps)."""
    from repro.common.pytree import global_norm
    text = jax.jit(global_norm).lower(
        {"t": jax.ShapeDtypeStruct(shape, jnp.float32)}).as_text()
    assert ("stablehlo.while" in text) == loops
    x = jnp.arange(12.0).reshape(3, 4)
    assert float(global_norm({"a": x, "b": x[None]})) == pytest.approx(
        float(jnp.sqrt(2 * jnp.sum(x * x))))


def test_packed_tables_refuse_a_mesh():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2, devices=jax.devices()[:1])
    trainer = DLRMTrainer(CFG, optimizer="rowwise_adagrad", lr=0.02, mesh=mesh)
    batch = trainer.put(_batch(CFG, 8, 0))
    with pytest.raises(NotImplementedError, match="one device"):
        trainer.step(0, batch)


def test_train_on_feed_tiny_dcn():
    """The served loop with the DCN config: packed tables, fixed bags of
    unequal sizes from the pipeline's featurization, the cross network;
    each dispatched step counts the ids it looked up and its pad ids."""
    trainer = DLRMTrainer(CFG, optimizer="rowwise_adagrad", lr=0.02)
    record = criteo_record(CFG, batch=64)
    assert record.hots == HOTS and record.vocabs == ROWS
    shapes = []
    run = train_on_feed(
        trainer, record, steps=3, tune_every=2,
        machine=MachineSpec(n_cpus=2, mem_mb=4096),
        on_batch=lambda i, p, b: shapes.append(b["sparse_ids"].shape),
        log_every=0)
    assert len(run.losses) == 3
    assert all(math.isfinite(x) for x in run.losses)
    assert shapes == [(64, sum(HOTS))] * 3
    # the warm-up's four steps and the three fed ones; 11 ids pad to 16
    assert run.spans["ids_looked_up"]["n"] == 7 * 64 * sum(HOTS)
    assert run.spans["bag_pad_ids"]["n"] == 7 * 64 * 5
    assert run.teardown["all_joined"] is True
