"""A toy architecture for the benchmark's own tests: a model of another
shape than the DLRM, brought to a copy of the benchmark as files alone.

Two tables of unequal rows (`rows`) in one stacked leaf, each feature
with its own hot size (`hot`, its first ids of the fed bag, modulo its
rows), summed; a dense projection `n_dense` -> `embed_dim`; one low-rank
cross layer x1 = x0 * (U (V x0) + b) + x0 of rank `rank` over the
concatenated features; a linear head; mean log loss. Its step runs under
its own scopes, its dispatch under a host span `toy_step`, and it counts
the rows it looks up (`toy_rows_looked_up`).

The program has no such model, so its trainer here stands in for the
program: it trains through the program's optimizer and train step, and
is written apart from the reference below.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import check
import reference

SCOPES = ("toy_lookup", "toy_cross", "toy_head", "optimizer_update",
          "table_update", "dense_update")
SPANS = ("toy_step",)
TABLE_LEAF, BOTTOM_LEAF, CROSS_LEAF, HEAD_LEAF = 0, 10, 20, 30


def _width(cfg: Dict) -> int:
    return cfg["embed_dim"] * (1 + len(cfg["rows"]))


# ------------------------------------------------------------ program ----
def _program_loss(params, batch, cfg: Dict):
    from repro.models.recsys import bce_loss
    with jax.named_scope("toy_lookup"):
        pooled, off = [], 0
        for f, (n, h) in enumerate(zip(cfg["rows"], cfg["hot"])):
            ids = batch["sparse_ids"][:, f, :h] % n + off
            pooled.append(jnp.take(params["tables"], ids, axis=0).sum(1))
            off += n
    with jax.named_scope("toy_cross"):
        d = batch["dense"] @ params["bottom"]["w"] + params["bottom"]["b"]
        x0 = jnp.concatenate([d] + pooled, axis=1)
        c = params["cross"]
        x1 = x0 * ((x0 @ c["v"]) @ c["u"] + c["b"]) + x0
    with jax.named_scope("toy_head"):
        logit = (x1 @ params["head"]["w"] + params["head"]["b"])[:, 0]
    loss = bce_loss(logit, batch["label"])
    return loss, {"bce": loss}


class _Trainer:
    """What `train_on_feed` and the harness ask of a trainer."""

    def __init__(self, cfg: Dict):
        from repro.train.optim import make_optimizer
        from repro.train.train_step import make_train_step
        self.opt = make_optimizer(cfg["optimizer"], lr=cfg["lr"],
                                  warmup=cfg["lr_warmup_steps"],
                                  total_steps=cfg["lr_total_steps"], eps=cfg["eps"],
                                  rowwise_min_elems=cfg["rowwise_min_elems"])
        self.params = init_params(reference.seed_words(0), cfg,
                                  jnp.dtype(cfg["param_dtype"]))
        self.opt_state = self.opt.init(self.params)
        self._step = jax.jit(make_train_step(lambda p, b: _program_loss(p, b, cfg),
                                             self.opt))
        self.batch_sharding = None
        self.rows_per_step = cfg["batch"] * sum(cfg["hot"])

    def put(self, batch):
        return {k: jnp.asarray(v) for k, v in batch.items()}

    def step(self, i, batch):
        from repro.common import tracing
        with tracing.span("toy_step"):
            self.params, self.opt_state, metrics = self._step(
                self.params, self.opt_state, i, batch)
        tracing.count("toy_rows_looked_up", self.rows_per_step)
        return metrics

    def warm_up(self, batch, iters: int = 3) -> float:
        self.step(0, batch)
        jax.block_until_ready(self.params)
        t0 = time.monotonic()
        for k in range(iters):
            self.step(k, batch)
        jax.block_until_ready(self.params)
        return (time.monotonic() - t0) / iters


def make_trainer(cfg: Dict, mesh):
    if mesh is not None:
        raise ValueError("the toy runs on one device")
    return _Trainer(cfg)


def record_spec(cfg: Dict, traffic: Dict, seed: int):
    from repro.data.featurize import RecordSpec
    return RecordSpec(batch=cfg["batch"], n_sparse=len(cfg["rows"]),
                      n_dense=cfg["n_dense"], vocab=max(cfg["rows"]),
                      hot=max(cfg["hot"]), seed=int(seed) % (1 << 32),
                      **traffic["record"])


def feed_faults(batches: List[Dict[str, np.ndarray]], cfg: Dict) -> List[str]:
    b = cfg["batch"]
    want = {"sparse_ids": ((b, len(cfg["rows"]), max(cfg["hot"])), np.int32),
            "dense": ((b, cfg["n_dense"]), np.float32), "label": ((b,), np.float32)}
    return check.feed_faults(batches, want, "sparse_ids", max(cfg["rows"]))


# ------------------------------------------------------------ weights ----
def table_rows(words, table, row, cfg: Dict, dtype):
    d = cfg["embed_dim"]
    k = jnp.arange(d, dtype=jnp.uint32)
    return reference.draw(words, TABLE_LEAF, table[:, None], row[:, None],
                          k[None, :], d ** -0.5, dtype)


def init_dense(words, cfg: Dict, dtype) -> Dict:
    w, r = _width(cfg), cfg["rank"]

    def matrix(leaf, a, b):
        it = [jax.lax.broadcasted_iota(jnp.uint32, (a, b), x) for x in (0, 1)]
        return reference.draw(words, leaf, 0, *it, a ** -0.5, dtype)
    return {"bottom": reference.draw_mlp(words, [cfg["n_dense"], cfg["embed_dim"]],
                                         BOTTOM_LEAF, dtype)[0],
            "cross": {"v": matrix(CROSS_LEAF, w, r), "u": matrix(CROSS_LEAF + 1, r, w),
                      "b": jnp.zeros((w,), dtype)},
            "head": reference.draw_mlp(words, [w, 1], HEAD_LEAF, dtype)[0]}


def init_params(words, cfg: Dict, dtype):
    """Both tables' rows one after the other in one leaf, and the rest."""
    table = np.repeat(np.arange(len(cfg["rows"])), cfg["rows"])
    row = np.concatenate([np.arange(n) for n in cfg["rows"]])
    return {reference.TABLES: table_rows(words, jnp.asarray(table), jnp.asarray(row),
                                         cfg, dtype),
            **init_dense(words, cfg, dtype)}


# ----------------------------------------------------------- reference ---
def forward(params, rows, dense, cfg: Dict, q: Callable = reference.identity):
    """rows (B, sum(hot), D): feature f's rows in its hot[f] columns."""
    ends = np.cumsum(cfg["hot"])
    pooled = [q(rows)[:, e - h:e].sum(1) for e, h in zip(ends, cfg["hot"])]
    x0 = q(jnp.concatenate(
        [q(dense) @ q(params["bottom"]["w"]) + q(params["bottom"]["b"])] + pooled, axis=1))
    c = params["cross"]
    low = q(x0 @ q(c["v"]))
    x1 = x0 * (q(low @ q(c["u"])) + q(c["b"])) + x0
    return (q(x1) @ q(params["head"]["w"]) + q(params["head"]["b"]))[:, 0]


def lookups(batch: Dict[str, np.ndarray], cfg: Dict):
    ids = np.asarray(batch["sparse_ids"], np.int64)
    cols = [ids[:, f, :h] % n for f, (n, h) in enumerate(zip(cfg["rows"], cfg["hot"]))]
    return (np.concatenate(cols, axis=1),
            np.repeat(np.arange(len(cfg["rows"])), cfg["hot"]))


def replay(seed: int, cfg: Dict, steps, *, quant: str = "float32",
           fault: str = "", shards: int = 1, device=None) -> Dict:
    rowwise = sum(cfg["rows"]) * cfg["embed_dim"] > cfg["rowwise_min_elems"]
    return reference.sparse_replay(
        seed, cfg, steps, rows=cfg["rows"], lookups=lambda b: lookups(b, cfg),
        draw_rows=table_rows, init_dense=init_dense, forward=forward,
        altered="head/w", rowwise=rowwise, quant=quant, fault=fault,
        shards=shards, device=device)


# -------------------------------------------------------------- counts ---
def _cross_flops(cfg: Dict) -> int:
    return 2 * 2 * _width(cfg) * cfg["rank"]


def train_flops_per_example(cfg: Dict) -> int:
    n, d, w = cfg["n_dense"], cfg["embed_dim"], _width(cfg)
    fwd = 2 * n * d + _cross_flops(cfg) + 2 * w
    return 3 * fwd - 2 * n * d


def scope_work(cfg: Dict) -> Dict[str, Dict[str, int]]:
    """The cross layer: its two products forward and backward; x0 read
    and x1 written forward, twice that backward, in float32."""
    b, w = cfg["batch"], _width(cfg)
    return {"toy_cross": {"ops": 3 * b * _cross_flops(cfg), "bytes": 3 * 2 * 4 * b * w}}
