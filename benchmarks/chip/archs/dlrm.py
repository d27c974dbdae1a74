"""The DLRM (Naumov et al., arXiv:1906.00091) as the program trains it:
everything the benchmark knows of this architecture, found by
`layout.arch("dlrm")` for a configuration whose `"model"` is "dlrm".

The configuration's keys: `n_sparse` tables of `rows_per_table` rows
each, stacked in one (n_sparse, rows, embed_dim) leaf; `multi_hot` ids
per table per example, summed; a bottom MLP `n_dense` -> `bottom_mlp`;
the n(n-1)/2 lower-triangle pairwise dots of the n = n_sparse + 1
feature vectors; a top MLP over those dots and the bottom MLP's output
-> `top_mlp` (ReLU between layers) -> logit; mean log loss.

The plain reference follows that description with the generic parts of
`reference.py`; nothing here imports the program but the trainer and
the record spec it is run with.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import check
import reference

# the step's named scopes (models/dlrm.forward, train/train_step,
# train/optim.rowwise_adagrad); the backward ops keep them
SCOPES = ("bottom_mlp", "embedding_lookup", "interaction", "top_mlp",
          "optimizer_update", "table_update", "dense_update")
TABLE_LEAF = 0
BOTTOM_LEAF = 10      # + layer index
TOP_LEAF = 20         # + layer index
ALTERED = "top/0/w"   # the leaf whose gradient the `grad_altered` fault doubles


# ------------------------------------------------------------ program ----
def _model_config(cfg: Dict):
    from repro.configs.base import DLRMConfig
    return DLRMConfig(
        name=cfg["name"], n_sparse=cfg["n_sparse"], n_dense=cfg["n_dense"],
        embed_dim=cfg["embed_dim"],
        vocab_sizes=(cfg["rows_per_table"],) * cfg["n_sparse"],
        bottom_mlp=tuple(cfg["bottom_mlp"]), top_mlp=tuple(cfg["top_mlp"]),
        multi_hot=cfg["multi_hot"], param_dtype=cfg["param_dtype"],
        tp_lookup=cfg["tp_lookup"],
        sharding_overrides=tuple((k, tuple(v) if isinstance(v, list) else v)
                                 for k, v in cfg["sharding_overrides"]))


def make_trainer(cfg: Dict, mesh):
    """The program's trainer for `cfg`, on one device (mesh None) or
    over `mesh`."""
    from repro.train.feed_loop import DLRMTrainer
    return DLRMTrainer(_model_config(cfg), optimizer=cfg["optimizer"],
                       lr=cfg["lr"], seed=0, mesh=mesh)


def record_spec(cfg: Dict, traffic: Dict, seed: int):
    """The raw records the pipeline featurizes for this model."""
    from repro.data.featurize import RecordSpec
    return RecordSpec(batch=cfg["batch"], n_sparse=cfg["n_sparse"],
                      n_dense=cfg["n_dense"], vocab=cfg["rows_per_table"],
                      hot=cfg["multi_hot"], seed=int(seed) % (1 << 32),
                      **traffic["record"])


def feed_faults(batches: List[Dict[str, np.ndarray]], cfg: Dict) -> List[str]:
    """What the fed batches break of featurization's guarantees."""
    b = cfg["batch"]
    want = {"sparse_ids": ((b, cfg["n_sparse"], cfg["multi_hot"]), np.int32),
            "dense": ((b, cfg["n_dense"]), np.float32), "label": ((b,), np.float32)}
    return check.feed_faults(batches, want, "sparse_ids", cfg["rows_per_table"])


# ------------------------------------------------------------ weights ----
def top_in(cfg: Dict) -> int:
    f = cfg["n_sparse"] + 1
    return f * (f - 1) // 2 + cfg["bottom_mlp"][-1]


def init_mlps(words, cfg: Dict, dtype) -> Dict:
    return {"bottom": reference.draw_mlp(
                words, [cfg["n_dense"]] + list(cfg["bottom_mlp"]), BOTTOM_LEAF, dtype),
            "top": reference.draw_mlp(
                words, [top_in(cfg)] + list(cfg["top_mlp"]), TOP_LEAF, dtype)}


def init_params(words, cfg: Dict, dtype):
    """The whole parameter tree, in the program's layout: tables
    (n_sparse, rows, embed_dim) and the two MLPs as tuples of {w, b}."""
    shape = (cfg["n_sparse"], cfg["rows_per_table"], cfg["embed_dim"])
    it = [jax.lax.broadcasted_iota(jnp.uint32, shape, a) for a in range(3)]
    tables = reference.draw(words, TABLE_LEAF, *it, cfg["embed_dim"] ** -0.5, dtype)
    return {reference.TABLES: tables, **init_mlps(words, cfg, dtype)}


def table_rows(words, table, row, cfg: Dict, dtype):
    """Rows (U, embed_dim) of the drawn tables at (table, row), as
    `init_params` draws them."""
    d = cfg["embed_dim"]
    k = jnp.arange(d, dtype=jnp.uint32)
    return reference.draw(words, TABLE_LEAF, table[:, None], row[:, None],
                          k[None, :], d ** -0.5, dtype)


# ----------------------------------------------------------- reference ---
def forward(params, rows, dense, cfg: Dict, q: Callable = reference.identity):
    """rows (B, F, hot, D) float32 -> logits (B,)."""
    x = q(dense)
    for layer in params["bottom"]:
        x = jax.nn.relu(q(x) @ q(layer["w"]) + q(layer["b"]))
    feats = jnp.concatenate([x[:, None], q(rows).sum(2)], axis=1)
    feats = q(feats)
    gram = jnp.einsum("bfd,bgd->bfg", feats, feats)
    ii, jj = np.tril_indices(feats.shape[1], k=-1)
    z = jnp.concatenate([gram[:, ii, jj], x], axis=-1)
    top = params["top"]
    for n, layer in enumerate(top):
        z = q(z) @ q(layer["w"]) + q(layer["b"])
        if n < len(top) - 1:
            z = jax.nn.relu(z)
    return z[:, 0]


def lookups(batch: Dict[str, np.ndarray]):
    """Every id of `sparse_ids` (B, F, hot), and its table: the feature."""
    ids = np.asarray(batch["sparse_ids"], np.int64)
    return ids, np.broadcast_to(np.arange(ids.shape[1])[:, None], ids.shape[1:])


def replay(seed: int, cfg: Dict, steps, *, quant: str = "float32",
           fault: str = "", shards: int = 1, device=None) -> Dict:
    """The float32 reference over `steps` (`reference.sparse_replay`),
    with the control's precision `quant` and the planted `fault`."""
    f, v = cfg["n_sparse"], cfg["rows_per_table"]
    # row-wise Adagrad serves a table leaf of more than rowwise_min_elems
    # elements; a smaller one is element-wise like the MLPs
    rowwise = f * v * cfg["embed_dim"] > cfg["rowwise_min_elems"]
    return reference.sparse_replay(
        seed, cfg, steps, rows=[v] * f, lookups=lookups, draw_rows=table_rows,
        init_dense=init_mlps, forward=forward, altered=ALTERED, rowwise=rowwise,
        quant=quant, fault=fault, shards=shards, device=device)


# -------------------------------------------------------------- counts ---
def _layers(cfg: Dict):
    bottom = [cfg["n_dense"]] + list(cfg["bottom_mlp"])
    top = [top_in(cfg)] + list(cfg["top_mlp"])
    return list(zip(bottom[:-1], bottom[1:])) + list(zip(top[:-1], top[1:]))


def pair_dots(cfg: Dict) -> int:
    f = cfg["n_sparse"] + 1
    return f * (f - 1) // 2


def forward_flops(cfg: Dict) -> int:
    mlp = sum(2 * a * b for a, b in _layers(cfg))
    return mlp + pair_dots(cfg) * 2 * cfg["embed_dim"]


def train_flops_per_example(cfg: Dict) -> int:
    """Operations one example's forward and backward need, as the
    algorithm needs them: each dense layer's product forward (2 * in *
    out), and backward twice that (the weight gradient and the input
    gradient) except the first bottom layer, whose input is data and
    needs no gradient; the interaction as its pair dots of length
    embed_dim (2 * D each), forward, and backward one product per
    operand. Gathers, the tables' scatter, biases, activations and the
    optimizer are element-wise or data movement and are not counted, as
    MFU conventionally does."""
    first_in, first_out = _layers(cfg)[0]
    backward = 2 * forward_flops(cfg) - 2 * first_in * first_out
    return forward_flops(cfg) + backward


def scope_work(cfg: Dict) -> Dict[str, Dict[str, int]]:
    """{scope: {"ops", "bytes"}} one train step needs, as the algorithm
    needs them and from the configuration alone (not what the program
    executes: its dense whole-table gradient and whole-table write are
    its choice).

    embedding_lookup: read the looked-up rows, write their pooled sums;
      backward, read-modify-write each looked-up row's gradient. Ops:
      the bags' sums and the gradient's scatter-add, one add an element.
    table_update: each looked-up row (batch x multi_hot per table, an
      upper bound on the distinct rows touched) and its Adagrad
      accumulator, read and written. Ops: row-wise, 5 an element
      (square, sum, two products, subtract) and 4 a row (mean,
      accumulate, eps, rsqrt); element-wise 7 an element."""
    d = cfg["embed_dim"]
    size = np.dtype(cfg["param_dtype"]).itemsize
    pooled = cfg["batch"] * cfg["n_sparse"]
    looked = pooled * cfg["multi_hot"]
    rowwise = cfg["n_sparse"] * cfg["rows_per_table"] * d > cfg["rowwise_min_elems"]
    acc = 4 if rowwise else 4 * d           # float32 accumulators
    return {
        "embedding_lookup": {"ops": d * (looked - pooled) + d * looked,
                             "bytes": size * d * (looked + pooled + 2 * looked)},
        "table_update": {"ops": looked * (5 * d + 4 if rowwise else 7 * d),
                         "bytes": 2 * looked * (size * d + acc)},
    }
