"""DLRM-DCNv2 (the MLPerf Training recommendation model since v3.0:
torchrec `DLRM_DCN` with `LowRankCrossNet`, trained on the multi-hot
Criteo 1TB data) as the program trains it: everything the benchmark
knows of this architecture, found by `layout.arch("dcnv2")` for a
configuration whose `"model"` is "dcnv2".

The configuration's keys: tables of `rows` rows each, one after another
in one (sum(rows), embed_dim) leaf; a fixed bag of `hot_sizes[f]` ids
for feature f, local to its table, summed; a bottom MLP `n_dense` ->
`bottom_mlp` (ReLU after every layer); x_0 the concatenation of its
output and the pooled bags ((1 + len(rows)) * embed_dim wide);
`dcn_layers` low-rank cross layers of rank `dcn_rank`,
x_{l+1} = x_0 * (W_l (V_l x_l) + b_l) + x_l; a top MLP over the last x
-> `top_mlp` (ReLU between layers) -> logit; mean log loss.

The plain reference (`forward`, `replay`) follows that description in
`jax.numpy` at the highest matmul precision, apart from the program; it
trains on the whole packed table (2.15 GB in float32 at the cell's
size). `reference.sparse_replay` would hold one row per looked-up id:
4 distinct batches x 16,384 x 214 ids = 14.0M rows, 7.2 GB and as much
again for their gradient, which one chip cannot hold. Nothing here
imports the program but the trainer and the record spec it is run with.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

import check
import reference

# the step's named scopes (models/dlrm.forward, models/embedding
# .packed_multifeature_bag, train/train_step, train/optim.rowwise_adagrad);
# the backward ops keep them
SCOPES = ("bottom_mlp", "embedding_lookup", "bag_lookup", "interaction",
          "cross_net", "top_mlp", "optimizer_update", "table_update",
          "dense_update")
TABLE_LEAF = 0
BOTTOM_LEAF = 10      # + layer index
CROSS_LEAF = 20       # + 2 * layer index (v), + 1 (w)
TOP_LEAF = 40         # + layer index
ALTERED = "top/0/w"   # the leaf whose gradient the `grad_altered` fault doubles
# the planted faults `replay` takes: `cross_skipped` leaves the cross
# layers out (the top MLP reads x_0)
FAULTS = ("", "half_batch", "grad_altered", "cross_skipped")


def width(cfg: Dict) -> int:
    """x_0's width: the bottom MLP's output and one pooled bag a table."""
    return (1 + len(cfg["rows"])) * cfg["embed_dim"]


def slot_offsets(cfg: Dict) -> np.ndarray:
    """(sum(hot_sizes),): the first packed row of each id column's table."""
    first = np.concatenate([[0], np.cumsum(cfg["rows"])[:-1]])
    return np.repeat(first, cfg["hot_sizes"]).astype(np.int32)


# ------------------------------------------------------------ program ----
def make_trainer(cfg: Dict, mesh):
    """The program's own `DLRMTrainer`, with the cross network and packed
    tables (one device: the program refuses packed tables on a mesh)."""
    from repro.configs.base import DLRMConfig
    from repro.train.feed_loop import DLRMTrainer
    model = DLRMConfig(
        name=cfg["name"], n_sparse=len(cfg["rows"]), n_dense=cfg["n_dense"],
        embed_dim=cfg["embed_dim"], vocab_sizes=tuple(cfg["rows"]),
        hot_sizes=tuple(cfg["hot_sizes"]), bottom_mlp=tuple(cfg["bottom_mlp"]),
        top_mlp=tuple(cfg["top_mlp"]), interaction="dcn",
        dcn_layers=cfg["dcn_layers"], dcn_rank=cfg["dcn_rank"],
        param_dtype=cfg["param_dtype"])
    return DLRMTrainer(model, optimizer=cfg["optimizer"], lr=cfg["lr"], seed=0,
                       mesh=mesh)


def record_spec(cfg: Dict, traffic: Dict, seed: int):
    """Raw records of fixed bags: feature f's list holds exactly
    hot_sizes[f] ids, hashed modulo rows[f]."""
    from repro.data.featurize import RecordSpec
    return RecordSpec(batch=cfg["batch"], n_sparse=len(cfg["rows"]),
                      n_dense=cfg["n_dense"], vocabs=tuple(cfg["rows"]),
                      hots=tuple(cfg["hot_sizes"]), seed=int(seed) % (1 << 32),
                      **traffic["record"])


def feed_faults(batches: List[Dict[str, np.ndarray]], cfg: Dict) -> List[str]:
    """What the fed batches break of featurization's guarantees; each id
    column is held to its own table's rows."""
    b = cfg["batch"]
    want = {"sparse_ids": ((b, sum(cfg["hot_sizes"])), np.int32),
            "dense": ((b, cfg["n_dense"]), np.float32), "label": ((b,), np.float32)}
    rows = np.repeat(np.asarray(cfg["rows"], np.int64), cfg["hot_sizes"])
    return check.feed_faults(batches, want, "sparse_ids", rows)


# ------------------------------------------------------------ weights ----
def _matrix(words, leaf: int, a: int, b: int, scale, dtype):
    r = jax.lax.broadcasted_iota(jnp.uint32, (a, b), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (a, b), 1)
    return reference.draw(words, leaf, 0, r, c, scale, dtype)


def init_dense(words, cfg: Dict, dtype) -> Dict:
    """The bottom and top MLPs (standard deviation fan_in^-1/2, biases
    0) and the cross layers (each kernel at torchrec's xavier standard
    deviation sqrt(2 / (width + rank)), biases 0)."""
    w, r = width(cfg), cfg["dcn_rank"]
    std = (2.0 / (w + r)) ** 0.5
    cross = tuple({"v": _matrix(words, CROSS_LEAF + 2 * n, w, r, std, dtype),
                   "w": _matrix(words, CROSS_LEAF + 2 * n + 1, r, w, std, dtype),
                   "b": jnp.zeros((w,), dtype)} for n in range(cfg["dcn_layers"]))
    return {"bottom": reference.draw_mlp(
                words, [cfg["n_dense"]] + list(cfg["bottom_mlp"]), BOTTOM_LEAF, dtype),
            "cross": cross,
            "top": reference.draw_mlp(words, [w] + list(cfg["top_mlp"]), TOP_LEAF, dtype)}


def init_params(words, cfg: Dict, dtype):
    """The whole parameter tree, in the program's layout: the packed
    tables (sum(rows), embed_dim), row r of table t drawn at (t, r), and
    the dense weights."""
    n, d = sum(cfg["rows"]), cfg["embed_dim"]
    first = jnp.asarray(np.cumsum(cfg["rows"])[:-1], jnp.int32)
    packed = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    table = jnp.sum(packed >= first[None, :], axis=1, keepdims=True, dtype=jnp.int32)
    start = jnp.concatenate([jnp.zeros((1,), jnp.int32), first])[table]
    k = jax.lax.broadcasted_iota(jnp.uint32, (1, d), 1)
    tables = reference.draw(words, TABLE_LEAF, table, packed - start, k, d ** -0.5, dtype)
    return {reference.TABLES: tables, **init_dense(words, cfg, dtype)}


# ----------------------------------------------------------- reference ---
def forward(params, ids, dense, cfg: Dict, q: Callable = reference.identity,
            cross: bool = True):
    """ids (B, sum(hot_sizes)) int32, local to each column's table;
    dense (B, n_dense) -> logits (B,). `q` rounds (the control); without
    `cross` the top MLP reads x_0."""
    x = q(dense)
    for layer in params["bottom"]:
        x = jax.nn.relu(q(x) @ q(layer["w"]) + q(layer["b"]))
    rows = q(params[reference.TABLES][ids + jnp.asarray(slot_offsets(cfg))])
    pooled, start = [], 0
    for h in cfg["hot_sizes"]:
        pooled.append(rows[:, start:start + h].sum(1))
        start += h
    x0 = q(jnp.concatenate([x] + pooled, axis=1))
    xl = x0
    if cross:
        for c in params["cross"]:
            low = q(q(xl) @ q(c["v"]))
            xl = x0 * (q(low @ q(c["w"])) + q(c["b"])) + xl
    z = xl
    top = params["top"]
    for n, layer in enumerate(top):
        z = q(z) @ q(layer["w"]) + q(layer["b"])
        if n < len(top) - 1:
            z = jax.nn.relu(z)
    return z[:, 0]


def _rowwise(leaf, cfg: Dict) -> bool:
    """As the program's row-wise Adagrad decides: one accumulator a row
    for a 2-D or larger leaf of more than rowwise_min_elems elements."""
    return leaf.ndim >= 2 and leaf.size > cfg["rowwise_min_elems"]


def _step(state, ids, dense, label, lr, *, cfg, q, fault):
    """One reference train step over the whole parameter tree."""
    params = {k: v for k, v in state.items() if k != "acc"}

    def loss_of(p):
        z = forward(p, ids, dense, cfg, q, cross=fault != "cross_skipped")
        if fault == "half_batch":
            h = z.shape[0] // 2
            return reference.log_loss(z[:h], label[:h])
        return reference.log_loss(z, label)

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_of)(params)
    if fault == "grad_altered":
        grads = jax.tree_util.tree_map_with_path(
            lambda path, g: g * 2.0 if reference.leaf_name(path) == ALTERED else g,
            grads)
    eps = float(cfg["eps"])

    def update(p, g, a):
        if _rowwise(p, cfg):
            a = a + jnp.mean(jnp.square(g), axis=-1)
            scale = jax.lax.rsqrt(a + eps)[..., None]
        else:
            a = a + jnp.square(g)
            scale = jax.lax.rsqrt(a + eps)
        return q(p - lr * g * scale), a
    flat_p, tree = jax.tree_util.tree_flatten(params)
    out = [update(p, g, a) for p, g, a in zip(
        flat_p, tree.flatten_up_to(grads), tree.flatten_up_to(state["acc"]))]
    new = jax.tree_util.tree_unflatten(tree, [p for p, _ in out])
    new["acc"] = jax.tree_util.tree_unflatten(tree, [a for _, a in out])
    return new, loss, reference.leaf_norms(grads)


@functools.lru_cache(maxsize=None)
def _compiled_step(cfg: "reference._Frozen", quant: str, fault: str):
    q = (reference.identity if quant == "float32"
         else reference._rounder(jnp.dtype(quant)))
    return jax.jit(functools.partial(_step, cfg=cfg, q=q, fault=fault),
                   donate_argnums=(0,), compiler_options=reference.EXACT)


def _state0(words, cfg: Dict):
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    init_params(words, cfg, jnp.dtype(cfg["param_dtype"])))
    acc = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape[:-1] if _rowwise(p, cfg) else p.shape, jnp.float32),
        params)
    return {**params, "acc": acc}


def _change(state, words, cfg: Dict):
    init = init_params(words, cfg, jnp.dtype(cfg["param_dtype"]))
    return reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b.astype(jnp.float32),
        {k: v for k, v in state.items() if k != "acc"}, init))


def replay(seed: int, cfg: Dict, steps, *, quant: str = "float32",
           fault: str = "", shards: int = 1, device=None) -> Dict:
    """The float32 reference over `steps` [(optimizer step index, host
    batch), ...] from the weights the seed draws, over the whole packed
    table; returns per step its loss and per-leaf gradient norms, and per
    leaf the norm of the parameters' change. `quant` is the control's
    precision; `fault` one of `FAULTS`."""
    if shards != 1:
        raise ValueError("the packed tables run on one device")
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; this architecture plants {FAULTS}")
    frozen = reference._Frozen(cfg)
    put = functools.partial(jax.device_put, device=device)
    words = put(reference.seed_words(seed))
    state = jax.jit(_state0, static_argnums=1, compiler_options=reference.EXACT)(
        words, frozen)
    fn = _compiled_step(frozen, quant, fault)
    losses, grads = [], []
    for step, batch in steps:
        state, loss, g = fn(state, put(np.asarray(batch["sparse_ids"], np.int32)),
                            put(np.asarray(batch["dense"], np.float32)),
                            put(np.asarray(batch["label"], np.float32)),
                            reference.learning_rate(cfg, step))
        losses.append(loss)
        grads.append(g)
    change = jax.jit(_change, static_argnums=2, compiler_options=reference.EXACT)(
        state, words, frozen)
    out = jax.device_get({"loss": losses, "grad": grads, "change": change})
    return {"loss": [float(x) for x in out["loss"]],
            "grad": [{k: float(x) for k, x in g.items()} for g in out["grad"]],
            "change": {k: float(x) for k, x in out["change"].items()}}


# -------------------------------------------------------------- counts ---
def _layers(cfg: Dict):
    bottom = [cfg["n_dense"]] + list(cfg["bottom_mlp"])
    top = [width(cfg)] + list(cfg["top_mlp"])
    return list(zip(bottom[:-1], bottom[1:])) + list(zip(top[:-1], top[1:]))


def cross_flops(cfg: Dict) -> int:
    """One example's cross layers forward: two products of width x rank
    a layer, 2 operations a multiply-add."""
    return cfg["dcn_layers"] * 2 * 2 * width(cfg) * cfg["dcn_rank"]


def forward_flops(cfg: Dict) -> int:
    return sum(2 * a * b for a, b in _layers(cfg)) + cross_flops(cfg)


def train_flops_per_example(cfg: Dict) -> int:
    """Operations one example's forward and backward need, by the DLRM
    architecture's convention (`archs/dlrm.train_flops_per_example`):
    each product forward, backward twice that (weight and input
    gradients), but no input gradient for the first bottom layer;
    gathers, the scatter, biases, element-wise products and the
    optimizer are not counted."""
    first_in, first_out = _layers(cfg)[0]
    return 3 * forward_flops(cfg) - 2 * first_in * first_out


def scope_work(cfg: Dict) -> Dict[str, Dict[str, int]]:
    """{scope: {"ops", "bytes"}} one train step needs, as the algorithm
    needs them and from the configuration alone.

    bag_lookup: as `archs/dlrm.scope_work` counts the lookup: read the
      looked-up rows (batch x sum(hot_sizes)), write their pooled sums
      (batch x tables); backward, read-modify-write each looked-up row's
      gradient. Ops: the bags' sums and the gradient's scatter-add, one
      add an element.
    cross_net: the cross layers' products forward and backward (three
      times the forward's). Each example's layers depend on that example
      alone, so the algorithm streams the batch once each way: x_0 read
      and the last x written forward; x_0 and the incoming gradient read
      and x_0's gradient written backward; each kernel read forward and
      backward and its gradient written. Bound by its operations."""
    d, b = cfg["embed_dim"], cfg["batch"]
    size = np.dtype(cfg["param_dtype"]).itemsize
    pooled = b * len(cfg["rows"])
    looked = b * sum(cfg["hot_sizes"])
    w, r, layers = width(cfg), cfg["dcn_rank"], cfg["dcn_layers"]
    return {
        "bag_lookup": {"ops": d * (looked - pooled) + d * looked,
                       "bytes": size * d * (looked + pooled + 2 * looked)},
        "cross_net": {"ops": 3 * b * cross_flops(cfg),
                      "bytes": size * (5 * b * w + 3 * layers * 2 * w * r)},
    }
