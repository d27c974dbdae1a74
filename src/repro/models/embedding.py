"""Sparse-embedding substrate for the recsys/DLRM families.

JAX has no native EmbeddingBag and only BCOO sparse — so this module IS the
system: multi-hot embedding-bag built from `jnp.take` + `jax.ops.segment_sum`
(the taxonomy-specified pattern), with row-sharded tables over the `model`
mesh axis (the paper's "hybrid parallelism [49]" layout for DLRM).

Two layouts are supported:
  - `stacked`: all n_sparse tables share one vocab size -> a single
    (n_sparse, rows, dim) array (best for sharding + the Pallas kernel path).
  - `packed`: per-feature vocab sizes and bag sizes -> every table one
    after another in one (sum(rows), dim) array, looked up by flat ids
    with per-column table offsets (`packed_multifeature_bag`; one device).
The assigned recsys configs use `stacked` with hashed ids (hash % rows), the
standard industrial trick (QR-hashing is the documented extension).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np


def init_table(rng, rows: int, dim: int, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else dim ** -0.5
    return jax.random.normal(rng, (rows, dim), dtype) * scale


def init_stacked_tables(rng, n_tables: int, rows: int, dim: int,
                        dtype=jnp.float32):
    """(n_tables, rows, dim); logical axes (None, 'table_rows', 'table_dim')."""
    return (jax.random.normal(rng, (n_tables, rows, dim), dtype) * dim ** -0.5,
            (None, "table_rows", "table_dim"))


def embedding_lookup(table, ids):
    """Plain single-hot lookup. table: (V, D); ids: (...) int32 -> (..., D)."""
    return jnp.take(table, ids, axis=0)


def tp_embedding_lookup(table, ids, mesh):
    """Vocab-sharded lookup via shard_map with SHARDED gradients.

    GSPMD partitions the forward gather of a vocab-sharded table fine, but
    its transpose materializes a full (V, D) f32 scatter target on every
    device (observed 4.4 GiB/device for the kimi-k2 vocab). Inside
    shard_map, each model-rank gathers rows it owns (masked) + psum; the
    autodiff transpose then scatters into the LOCAL (V/tp, D) shard only.

    table: (V, D) sharded P('model', None); ids: (B, ...) int32 sharded
    over the data axes. Falls back to plain take when no usable mesh.
    """
    if mesh is None or "model" not in mesh.axis_names:
        return jnp.take(table, ids, axis=0)
    tp = mesh.shape["model"]
    v = table.shape[0]
    if tp == 1 or v % tp != 0:
        return jnp.take(table, ids, axis=0)
    v_loc = v // tp

    P = jax.sharding.PartitionSpec

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    lead = dp_axes if ids.shape[0] % max(dp, 1) == 0 and dp > 1 else None
    if isinstance(lead, tuple) and len(lead) == 1:
        lead = lead[0]
    ids_spec = P(lead, *([None] * (ids.ndim - 1)))
    out_spec = P(lead, *([None] * ids.ndim))

    def f(tbl, idl):
        row0 = jax.lax.axis_index("model") * v_loc
        lid = idl - row0
        ok = (lid >= 0) & (lid < v_loc)
        e = jnp.take(tbl, jnp.clip(lid, 0, v_loc - 1), axis=0)
        e = e * ok[..., None].astype(e.dtype)
        return jax.lax.psum(e, "model")

    return jax.shard_map(f, mesh=mesh, in_specs=(P("model", None), ids_spec),
                         out_specs=out_spec, check_vma=False)(table, ids)


def embedding_bag(table, ids, *, combiner: str = "sum", weights=None):
    """EmbeddingBag over the last axis of ids.

    table: (V, D); ids: (..., bag) int32 -> (..., D).
    combiner: "sum" | "mean" | "max". `weights` (..., bag) optional per-id
    weights (sum/mean only).
    """
    emb = jnp.take(table, ids, axis=0)          # (..., bag, D)
    if weights is not None:
        emb = emb * weights[..., None].astype(emb.dtype)
    if combiner == "sum":
        return jnp.sum(emb, axis=-2)
    if combiner == "mean":
        return jnp.mean(emb, axis=-2)
    if combiner == "max":
        return jnp.max(emb, axis=-2)
    raise ValueError(combiner)


def ragged_embedding_bag(table, ids, segment_ids, n_segments: int, *,
                         combiner: str = "sum"):
    """Ragged EmbeddingBag: flat ids + segment ids (torch-EmbeddingBag shape).

    table: (V, D); ids: (N,) int32; segment_ids: (N,) int32 sorted.
    Returns (n_segments, D). This is the `jnp.take` + `segment_sum`
    formulation the assignment calls for.
    """
    emb = jnp.take(table, ids, axis=0)          # (N, D)
    if combiner == "max":
        return jax.ops.segment_max(emb, segment_ids, num_segments=n_segments)
    out = jax.ops.segment_sum(emb, segment_ids, num_segments=n_segments)
    if combiner == "mean":
        cnt = jax.ops.segment_sum(jnp.ones_like(segment_ids, jnp.float32),
                                  segment_ids, num_segments=n_segments)
        out = out / jnp.maximum(cnt, 1.0)[..., None]
    return out


def multifeature_bag(tables, ids, *, combiner: str = "sum"):
    """Stacked-table multi-hot lookup.

    tables: (F, V, D); ids: (B, F, bag) int32 (already hashed mod V).
    Returns (B, F, D). Contracts the bag axis per feature.
    """
    f = tables.shape[0]
    # vmap over the feature axis so each feature reads its own table.
    def per_feature(tbl, idf):      # tbl: (V, D); idf: (B, bag)
        return embedding_bag(tbl, idf, combiner=combiner)
    out = jax.vmap(per_feature, in_axes=(0, 1), out_axes=1)(
        tables, ids)                # (B, F, D)
    return out


def slot_pad(n_slots: int) -> int:
    """Pad columns `packed_multifeature_bag` adds to `n_slots` id columns
    so that the slot axis fills whole (8, 128) float32 tiles."""
    return -n_slots % 8


def packed_multifeature_bag(table, ids, slot_offsets, hots):
    """Packed-table multi-hot lookup, one fixed bag size per feature.

    table: (sum(rows), D), every feature's table one after another;
    ids: (B, sum(hots)) int32, feature f's bag in its hots[f] columns,
    each id a row of its own table; slot_offsets: (sum(hots),) the first
    row of each column's table in `table` (static). Returns (B, F, D),
    each bag's sum.

    The gathered rows stay (B, S', D) with S' = sum(hots) padded to whole
    tiles (`slot_pad`), so neither the reshape of the flat gather nor its
    transpose copies them; the pad columns read distinct valid rows, so
    their (zero) gradients do not pile onto one row. All bags are summed
    by one contraction with a static (F, S') 0/1 matrix, 1 where column
    j holds an id of bag f and 0 on pad columns, at the highest
    precision: each product is 1*x or 0*x, exact in float32, and the
    rows' gradient is a single 1*g. The ids lie in their tables (the
    featurizer's modulo), so the gather clips rather than masks.
    """
    with jax.named_scope("bag_lookup"):
        b, s = ids.shape
        flat = ids + jnp.asarray(slot_offsets, jnp.int32)
        pad = slot_pad(s)
        if pad:
            fill = jnp.arange(b * pad, dtype=jnp.int32).reshape(b, pad)
            flat = jnp.concatenate([flat, fill % table.shape[0]], axis=1)
        rows = jnp.take(table, flat, axis=0, mode="clip")   # (B, S', D)
        ends, col = np.cumsum(hots), np.arange(s + pad)
        bags = (col >= (ends - np.asarray(hots))[:, None]) & (col < ends[:, None])
        return jnp.einsum("fj,bjd->bfd", jnp.asarray(bags, table.dtype), rows,
                          precision=jax.lax.Precision.HIGHEST)


def tp_multifeature_bag(tables, ids, mesh, *, combiner: str = "sum"):
    """Fully-row-sharded stacked-table lookup via shard_map (§Perf 1).

    Rows shard over EVERY mesh axis (Meta row-wise table sharding — the
    only layout where neither the table nor its gradient is ever
    replicated). The exchange per step:
      1. all_gather the int32 ids over the data axes (cheap: ids are tiny),
      2. each device looks up the FULL batch against its local row shard
         (masked gather, zero elsewhere),
      3. psum_scatter over the data axes returns each data-rank its own
         batch slice, already summed; one psum over `model` finishes.
    Wire bytes ~ one pass of the (B, F, D) embeddings in table dtype,
    vs GSPMD's full-batch f32 all-reduce + all-to-all (measured 12x
    reduction on dlrm-criteo/train_batch). The autodiff transpose is
    all_gather(d_out) + LOCAL scatter into the row shard, so table grads
    stay sharded and the optimizer runs shard-local.

    tables: (F, V, D) sharded P(None, (pod, data, model), None);
    ids: (B, F, hot) sharded over the data axes.
    """
    names = getattr(mesh, "axis_names", ()) if mesh is not None else ()
    if "model" not in names:
        return multifeature_bag(tables, ids, combiner=combiner)
    shard_axes = tuple(a for a in ("pod", "data", "model") if a in names)
    n_shards = 1
    for a in shard_axes:
        n_shards *= mesh.shape[a]
    v = tables.shape[1]
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    if n_shards == 1 or v % n_shards != 0 or ids.shape[0] % max(dp, 1):
        return multifeature_bag(tables, ids, combiner=combiner)
    v_loc = v // n_shards

    P = jax.sharding.PartitionSpec

    lead = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    ids_spec = P(lead, None, None)
    row_axes = shard_axes if len(shard_axes) > 1 else shard_axes[0]

    hot = ids.shape[-1]

    def _local_ids(idl):
        """(full-batch local ids, validity mask) for this row shard."""
        flat = jnp.zeros((), jnp.int32)
        for a in shard_axes:   # major-to-minor = shard_axes order
            flat = flat * mesh.shape[a] + jax.lax.axis_index(a)
        row0 = flat * v_loc
        if dp > 1:
            ids_full = jax.lax.all_gather(idl, dp_axes, axis=0, tiled=True)
        else:
            ids_full = idl
        lid = ids_full - row0
        ok = (lid >= 0) & (lid < v_loc)
        return jnp.clip(lid, 0, v_loc - 1), ok

    def fwd_local(tbl, idl):         # tbl: (F, v_loc, D); idl: (B_loc,F,hot)
        lid, ok = _local_ids(idl)

        def per_feature(t_f, id_f, ok_f):     # (v_loc, D), (B, hot)
            e = jnp.take(t_f, id_f, axis=0)   # (B, hot, D)
            e = e * ok_f[..., None].astype(e.dtype)
            return jnp.sum(e, axis=-2)
        out = jax.vmap(per_feature, in_axes=(0, 1, 1), out_axes=1)(
            tbl, lid, ok)                      # (B, F, D) partial
        if dp > 1:
            out = jax.lax.psum_scatter(out, dp_axes, scatter_dimension=0,
                                       tiled=True)   # (B_loc, F, D)
        out = jax.lax.psum(out, "model")
        if combiner == "mean":
            out = out / hot
        return out

    def bwd_local(d_out, idl):
        """Explicit transpose: bf16 all-gather of d_out + LOCAL scatter.
        (XLA's auto-transpose fuses the optimizer's f32 convert INTO the
        gather — 2x the wire bytes; measured on dlrm-criteo.)"""
        lid, ok = _local_ids(idl)
        g = d_out.astype(tables.dtype)
        if combiner == "mean":
            g = g / hot
        if dp > 1:
            g = jax.lax.all_gather(g, dp_axes, axis=0, tiled=True)

        def per_feature(id_f, ok_f, g_f):     # (B, hot), (B, hot), (B, D)
            upd = jnp.broadcast_to(g_f[:, None, :],
                                   (g_f.shape[0], hot, g_f.shape[1]))
            upd = upd * ok_f[..., None].astype(upd.dtype)
            return jnp.zeros((v_loc, g_f.shape[1]), g_f.dtype).at[
                id_f.reshape(-1)].add(upd.reshape(-1, g_f.shape[1]))
        return jax.vmap(per_feature, in_axes=(1, 1, 1), out_axes=0)(
            lid, ok, g)                        # (F, v_loc, D)

    fwd_sm = jax.shard_map(fwd_local, mesh=mesh,
                           in_specs=(P(None, row_axes, None), ids_spec),
                           out_specs=ids_spec, check_vma=False)
    bwd_sm = jax.shard_map(bwd_local, mesh=mesh,
                           in_specs=(ids_spec, ids_spec),
                           out_specs=P(None, row_axes, None),
                           check_vma=False)

    @jax.custom_vjp
    def lookup(tbl, idl):
        return fwd_sm(tbl, idl)

    def lookup_fwd(tbl, idl):
        return fwd_sm(tbl, idl), idl

    def lookup_bwd(idl, d_out):
        return bwd_sm(d_out, idl), None

    lookup.defvjp(lookup_fwd, lookup_bwd)
    return lookup(tables, ids)


def hash_ids(raw_ids, rows: int):
    """Cheap multiplicative hash into the table row space (mod rows)."""
    h = raw_ids.astype(jnp.uint32) * jnp.uint32(2654435761)
    return (h % jnp.uint32(rows)).astype(jnp.int32)
