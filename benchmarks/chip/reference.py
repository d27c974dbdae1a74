"""The benchmark's plain float32 reference: what every architecture's
module under `archs/` builds its reference from.

Nothing here imports the program or takes anything it made. Weights are
drawn from the run's seed by a counter-based hash (`draw`), so any
single element, or any row of a table, can be drawn on its own: the
harness puts the whole drawn parameter tree into the trainer, and the
reference draws again only the table rows that the checked batches look
up.

`sparse_replay` trains on the sparse state: `rows` holds only the
distinct (table, id) rows that the replayed batches touch, in float32,
plus one padding row. A row no batch touches has a zero gradient, so
Adagrad leaves it and its accumulator as they are: the sparse replay is
the dense one restricted to the rows that move. The optimizer is the one
the configuration states: row-wise Adagrad (one accumulator per table
row, the mean of the squared gradient over the row) on tables the
architecture says are row-wise, element-wise Adagrad on everything else,
with a linear warm-up then cosine learning rate. An architecture brings
its table sizes, where each looked-up id lives, how a row is drawn, its
dense weights and its forward pass; `archs/dlrm.py` shows the binding.

`quant` puts a lower precision in the reference's place (the control):
every parameter, activation and gathered row is rounded through it
before each product, and parameters are stored in it after each update;
gradients flow in float32.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_GOLD = 0x9E3779B9
_M1, _M2, _M3 = 0x7FEB352D, 0x846CA68B, 0x85EBCA6B
# Every program of the benchmark's own rounds where it says it does: by
# default the compiler may skip a float32 -> bf16 -> float32 round trip
# inside a fusion, and the chip's compiler does, so a draw recomputed
# inside a reduction would not equal the same draw stored in bf16.
EXACT = {"xla_allow_excess_precision": False}
TABLES = "tables"     # the parameter leaf that holds every table's rows


# ------------------------------------------------------------ weights ----
def seed_words(seed: int) -> np.ndarray:
    """The seed as two uint32 words (a traced argument, so one compiled
    program draws the weights of every seed)."""
    s = int(seed) % (1 << 64)
    return np.array([s & 0xFFFFFFFF, s >> 32], np.uint32)


def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(_M2)
    return x ^ (x >> 16)


def _bits(words, leaf: int, i, j, k):
    """uint32 hash of (seed, leaf, i, j, k); i, j, k broadcast."""
    u32 = lambda a: jnp.asarray(a).astype(jnp.uint32)
    h = _mix(words[0] + jnp.uint32((leaf * _GOLD) & 0xFFFFFFFF))
    h = _mix(h ^ words[1])
    h = _mix(h + u32(i))
    h = _mix(h ^ (u32(j) * jnp.uint32(_M3)))
    return _mix(h + u32(k))


def draw(words, leaf, i, j, k, scale, dtype):
    """Uniform values of standard deviation `scale`, rounded once to
    `dtype`. (h >> 9) | 1.0's exponent is exactly a float in [1, 2), so
    each value is one rounding of an exact number: a draw of one element
    equals the same element of a whole-tensor draw, on any backend."""
    h = _bits(words, leaf, i, j, k)
    f = jax.lax.bitcast_convert_type((h >> 9) | jnp.uint32(0x3F800000),
                                     jnp.float32) - 1.5
    return (f * jnp.float32(2.0 * math.sqrt(3.0) * scale)).astype(dtype)


def draw_mlp(words, dims: Sequence[int], leaf0: int, dtype):
    """Dense layers dims[0] -> dims[1] -> ...: each {w, b}, w drawn at
    standard deviation fan_in^-1/2 under leaf `leaf0` + its index, b 0."""
    layers = []
    for n, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        r = jax.lax.broadcasted_iota(jnp.uint32, (a, b), 0)
        c = jax.lax.broadcasted_iota(jnp.uint32, (a, b), 1)
        layers.append({"w": draw(words, leaf0 + n, 0, r, c, a ** -0.5, dtype),
                       "b": jnp.zeros((b,), dtype)})
    return tuple(layers)


# ------------------------------------------------------------- forward ---
def identity(x):
    return x


def _rounder(dtype):
    """Round values through `dtype`; the gradient passes unrounded (a
    float8 cotangent would underflow to zero at these loss scales, and a
    control that only ever reads zero would test nothing finer)."""
    def q(x):
        return x + jax.lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)
    return q


# the control's precision: the nearest below the one the configuration
# states for its parameters
CONTROL = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def log_loss(z, y):
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def learning_rate(opt: Dict, step):
    """Linear warm-up to `lr`, then cosine down to `lr_min_frac` of it."""
    step = jnp.asarray(step, jnp.float32)
    base, warm, total = opt["lr"], opt["lr_warmup_steps"], opt["lr_total_steps"]
    frac = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = base * (opt["lr_min_frac"] + (1 - opt["lr_min_frac"]) * 0.5
                  * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < warm, base * step / max(warm, 1), cos)


# -------------------------------------------------------------- replay ---


def _step(state, inv, dense, label, keep, lr, *, cfg, rowwise, q, fault,
          forward, altered):
    """One reference train step on the sparse state.

    inv (B, ...) indexes `state["tables"]`; keep (U+1,) zeroes looked
    up rows (all ones but under the `exchange_left_out` fault)."""
    def loss_of(params):
        rows = params[TABLES][inv] * keep[inv][..., None]
        z = forward(params, rows, dense, cfg, q)
        if fault == "half_batch":
            h = z.shape[0] // 2
            return log_loss(z[:h], label[:h])
        return log_loss(z, label)

    params = {k: v for k, v in state.items() if k != "acc"}
    dense_keys = [k for k in params if k != TABLES]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_of)(params)
    if fault == "grad_altered":
        grads = jax.tree_util.tree_map_with_path(
            lambda path, g: g * 2.0 if leaf_name(path) == altered else g, grads)
    acc, eps = state["acc"], float(cfg["eps"])
    g_tab = grads[TABLES]
    if rowwise:
        acc_tab = acc[TABLES] + jnp.mean(jnp.square(g_tab), axis=-1)
        scale = jax.lax.rsqrt(acc_tab + eps)[:, None]
    else:
        acc_tab = acc[TABLES] + jnp.square(g_tab)
        scale = jax.lax.rsqrt(acc_tab + eps)
    tables = state[TABLES] - lr * g_tab * scale
    acc_dense = jax.tree_util.tree_map(lambda a, g: a + jnp.square(g),
                                       {k: acc[k] for k in dense_keys},
                                       {k: grads[k] for k in dense_keys})
    weights = jax.tree_util.tree_map(
        lambda p, g, a: p - lr * g * jax.lax.rsqrt(a + eps),
        {k: state[k] for k in dense_keys},
        {k: grads[k] for k in dense_keys}, acc_dense)
    if q is not identity:
        tables = q(tables)
        weights = jax.tree_util.tree_map(q, weights)
    new = {TABLES: tables, **weights, "acc": {TABLES: acc_tab, **acc_dense}}
    return new, loss, leaf_norms(grads)


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """{path: float32 L2 norm} over the leaves of a parameter tree."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[leaf_name(path)] = jnp.sqrt(jnp.sum(jnp.square(
            leaf.astype(jnp.float32))))
    return out


def leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


@functools.lru_cache(maxsize=None)
def _compiled_step(cfg: "_Frozen", rowwise: bool, quant: str, fault: str,
                   forward: Callable, altered: str):
    q = identity if quant == "float32" else _rounder(jnp.dtype(quant))
    return jax.jit(functools.partial(_step, cfg=cfg, rowwise=rowwise, q=q,
                                     fault=fault, forward=forward,
                                     altered=altered),
                   donate_argnums=(0,), compiler_options=EXACT)


def _rows0(words, table, row, n, draw_rows, cfg, dtype):
    """The drawn rows at (table, row) in float32, zero past the first n
    and in the padding row."""
    rows = draw_rows(words, table, row, cfg, dtype).astype(jnp.float32)
    valid = jnp.arange(table.shape[0]) < n
    return jnp.concatenate([jnp.where(valid[:, None], rows, 0.0),
                            jnp.zeros((1, rows.shape[1]), jnp.float32)])


def _table_change(rows, words, table, row, n, draw_rows, cfg, dtype):
    return jnp.sqrt(jnp.sum(jnp.square(
        rows - _rows0(words, table, row, n, draw_rows, cfg, dtype))))


def sparse_replay(seed: int, cfg: Dict, steps: Sequence[Tuple[int, Dict]], *,
                  rows: Sequence[int], lookups: Callable, draw_rows: Callable,
                  init_dense: Callable, forward: Callable, altered: str,
                  rowwise: bool, quant: str = "float32", fault: str = "",
                  shards: int = 1, device=None) -> Dict:
    """Replay `steps` [(optimizer step index, host batch), ...] from the
    weights the seed draws for configuration `cfg` (its optimizer and
    parameter dtype); returns per step its loss and per-leaf gradient
    norms, and per leaf the norm of the parameters' change.

    The architecture brings:
      rows        each table's row count; the tables lie one after
                  another in the parameter leaf `tables`;
      lookups     batch -> (ids (B, ...) int, table (...) int): each
                  looked-up id and the table it indexes;
      draw_rows   (words, table (U,), row (U,), cfg, dtype) -> (U, D):
                  the rows as the architecture's weights draw them;
      init_dense  (words, cfg, dtype) -> the other parameters, a dict;
      forward     (params, rows (B, ..., D) float32, dense, cfg, q) ->
                  logits (B,), where rows are those `lookups` names, in
                  its shape, and q rounds (the control);
      altered     the leaf whose gradient `grad_altered` doubles;
      rowwise     whether the tables' Adagrad is row-wise.
    `forward` and `draw_rows` are module-level functions: the compiled
    step is kept by them.

    Faults: `half_batch` takes the loss over the first half of the batch;
    `grad_altered` doubles one gradient where the backward produces it;
    `exchange_left_out` zeroes the rows that a row-sharded lookup would
    fetch from the other `model` rank: the ids in row shards of odd
    index, of `shards` equal shards of their table."""
    dtype = jnp.dtype(cfg["param_dtype"])
    sizes = np.asarray(rows, np.int64)
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    distinct: List[Dict] = []
    for _, b in steps:
        if not any(b is d for d in distinct):
            distinct.append(b)
    flat = []
    for b in distinct:
        ids, table = lookups(b)
        flat.append(np.asarray(ids, np.int64) + offsets[table])
    keys, inv = np.unique(np.concatenate([f.reshape(-1) for f in flat]),
                          return_inverse=True)
    table = np.searchsorted(offsets, keys, side="right") - 1
    row = keys - offsets[table]
    bounds = np.cumsum([0] + [f.size for f in flat])
    # fixed size: one compiled program whatever the overlap of the batches
    cap = int(bounds[-1])
    keep = np.ones(cap + 1, np.float32)
    if fault == "exchange_left_out":
        keep[:keys.size] = (row // (sizes[table] // shards)) % 2 == 0
    put = functools.partial(jax.device_put, device=device)
    words = put(seed_words(seed))
    pad = lambda a: put(np.concatenate([a, np.zeros(cap - a.size, np.int64)]
                                       ).astype(np.int32))
    table_d, row_d = pad(table), pad(row)
    frozen = _Frozen(cfg)
    rows0 = jax.jit(_rows0, static_argnums=(4, 5, 6), compiler_options=EXACT)(
        words, table_d, row_d, keys.size, draw_rows, frozen, dtype)
    dense0 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    jax.jit(lambda w: init_dense(w, cfg, dtype),
                                            compiler_options=EXACT)(words))
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
    acc_shape = (cap + 1,) if rowwise else (cap + 1, rows0.shape[1])
    state = {TABLES: rows0, **jax.tree_util.tree_map(jnp.copy, dense0),
             "acc": {TABLES: jnp.zeros(acc_shape, jnp.float32), **zeros(dense0)}}
    fn = _compiled_step(frozen, rowwise, quant, fault, forward, altered)
    keep_d = put(keep)
    invs = [put(inv[bounds[k]:bounds[k + 1]].reshape(f.shape).astype(np.int32))
            for k, f in enumerate(flat)]
    losses, grads = [], []
    for step, batch in steps:
        k = next(n for n, d in enumerate(distinct) if d is batch)
        state, loss, g = fn(state, invs[k], put(np.asarray(batch["dense"], np.float32)),
                            put(np.asarray(batch["label"], np.float32)), keep_d,
                            learning_rate(cfg, step))
        losses.append(loss)
        grads.append(g)
    change = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, {k: state[k] for k in dense0}, dense0))
    change[TABLES] = jax.jit(_table_change, static_argnums=(5, 6, 7),
                             compiler_options=EXACT)(
        state[TABLES], words, table_d, row_d, keys.size, draw_rows, frozen, dtype)
    out = jax.device_get({"loss": losses, "grad": grads, "change": change})
    return {"loss": [float(x) for x in out["loss"]],
            "grad": [{k: float(x) for k, x in g.items()} for g in out["grad"]],
            "change": {k: float(x) for k, x in out["change"].items()}}


class _Frozen(dict):
    """A configuration dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True, default=str))


def gaps(program: Dict[str, float], ref: Dict[str, float],
         ref_grad: Dict[str, float]) -> Tuple[float, str]:
    """Worst leaf's |program norm - reference norm| over the larger of
    the reference's norm of that leaf and of the median leaf. Leaves
    whose reference gradient is under a thousandth of the median leaf's
    move by round-off alone and are left out."""
    med_g = float(np.median(list(ref_grad.values())))
    out = [k for k, g in ref_grad.items() if g < 1e-3 * med_g]
    med = float(np.median([ref[k] for k in ref]))
    worst, name = 0.0, ""
    for k in ref:
        if k in out:
            continue
        gap = abs(program[k] - ref[k]) / max(ref[k], med)
        if gap >= worst:
            worst, name = gap, k
    return worst, name
