"""Pallas TPU embedding-bag: fused multi-hot gather + reduce.

TPU adaptation (DESIGN.md §3): there is no native EmbeddingBag; the hot
loop is an HBM->VMEM row gather feeding the VPU. Each grid step's ids
arrive in SMEM as an (8, bag) block, so the kernel knows every row it
will read before it issues the reads.

Tiling: one grid step owns `_ROWS` = 8 output rows, which fills the
8-sublane (8, 128) f32 tile the TPU compiler requires of every block — a
(1, D) block per output row is refused for the chip. The table row of a
32-bit table is read as a single row; a narrower dtype (bf16) is stored
in HBM in 8-row tiles, so the kernel reads the aligned `_window` of rows
that holds the id and selects the row in-kernel (a `where` + sum over the
window, exact in f32).

`embedding_bag` is the row-DMA baseline: the table stays in HBM and grid
(B/8, bag) issues 8 window DMAs per step, revisiting each output block
`bag` times (TPU grids are sequential, so cross-step accumulation into
the same output block is the standard reduction pattern).
`embedding_bag_fused` is the perf variant for VMEM-resident tables:
grid (B/8,), the table bound once in VMEM, each step gathering and
summing whole bags in-kernel — bag x fewer grid steps and no output
read-modify-write. Accumulation order over j is identical in both, so
results match bit-for-bit (guarded by tests/test_kernels.py parity).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# output rows per grid step: one full sublane tile of the f32 output
_ROWS = 8


def _window(dtype) -> int:
    """Rows read per lookup: 1 for 32-bit tables; narrower dtypes are
    tiled 8 rows deep in HBM and a DMA slice must be tile-aligned."""
    return 1 if jnp.dtype(dtype).itemsize == 4 else 8


def _prepare(table, ids):
    """Pad ids to whole grid steps (row 0 is a valid pad id) and the
    table to whole windows; returns (table, ids, true batch, window)."""
    b = ids.shape[0]
    pad_b = -b % _ROWS
    if pad_b:
        ids = jnp.pad(ids, ((0, pad_b), (0, 0)))
    win = _window(table.dtype)
    pad_v = -table.shape[0] % win
    if pad_v:
        table = jnp.pad(table, ((0, pad_v), (0, 0)))
    return table, ids, b, win


def _pick(rows, k):
    """Row k of a (win, D) window as (1, D) f32, exactly."""
    rows = rows.astype(jnp.float32)
    if rows.shape[0] == 1:
        return rows
    sel = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == k
    return jnp.sum(jnp.where(sel, rows, 0.0), axis=0, keepdims=True)


def _ids_spec(bag: int) -> pl.BlockSpec:
    """The ids of one grid step's 8 bags, (8, bag) in SMEM. Prefetching
    the whole id array instead would pad it to 128 lanes: 32 MiB of the
    1 MiB SMEM at batch 65,536."""
    return pl.BlockSpec((_ROWS, bag), lambda i, *_: (i, 0),
                        memory_space=pltpu.SMEM)


def _kernel(ids_ref, table_hbm, out_ref, buf, sems, *, bag: int, win: int,
            combiner: str):
    j = pl.program_id(1)
    ids = [ids_ref[r, j] for r in range(_ROWS)]
    copies = [
        pltpu.make_async_copy(
            table_hbm.at[pl.ds(pl.multiple_of(idx // win * win, win), win), :],
            buf.at[r], sems.at[r])
        for r, idx in enumerate(ids)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    for r, idx in enumerate(ids):
        out_ref[pl.ds(r, 1), :] += _pick(buf[r], idx % win)

    if combiner == "mean":
        @pl.when(j == bag - 1)
        def _final():
            out_ref[...] = out_ref[...] / bag


def embedding_bag(table, ids, *, combiner: str = "sum",
                  interpret: bool = False):
    """table: (V, D) f32/bf16; ids: (B, bag) int32 -> (B, D) f32.

    Accumulates in f32 (sum of bf16 rows loses mass for large bags).
    """
    table, ids, b, win = _prepare(table, ids)
    bp, bag = ids.shape
    d = table.shape[1]
    kernel = functools.partial(_kernel, bag=bag, win=win, combiner=combiner)
    out = pl.pallas_call(
        kernel,
        grid=(bp // _ROWS, bag),
        in_specs=[_ids_spec(bag), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((_ROWS, d), lambda i, j: (i, 0)),
        scratch_shapes=[pltpu.VMEM((_ROWS, win, d), table.dtype),
                        pltpu.SemaphoreType.DMA((_ROWS,))],
        out_shape=jax.ShapeDtypeStruct((bp, d), jnp.float32),
        interpret=interpret,
    )(ids, table)
    return out[:b]


# the fused variant keeps the WHOLE table resident in VMEM, so it only
# fires when the table fits comfortably (v5e's default scoped VMEM limit
# is 16 MiB; stay at half to leave room for the output blocks)
_FUSED_MAX_TABLE_BYTES = 8 * 1024 * 1024
# unroll bound for the in-kernel bag loop
_FUSED_MAX_BAG = 16


def _fused_kernel(ids_ref, table_ref, out_ref, *, bag: int, win: int,
                  combiner: str):
    """One grid step = 8 output rows: gather + sum each whole bag.

    Same j-ascending, f32 accumulation order as the baseline's grid
    revisits — the two variants are bit-identical, not just close."""
    def row(r, j):
        idx = ids_ref[r, j]
        start = pl.multiple_of(idx // win * win, win)
        return _pick(table_ref[pl.ds(start, win), :], idx % win)

    for r in range(_ROWS):
        acc = row(r, 0)
        for j in range(1, bag):
            acc = acc + row(r, j)
        if combiner == "mean":
            acc = acc / bag
        out_ref[pl.ds(r, 1), :] = acc


def embedding_bag_fused(table, ids, *, combiner: str = "sum",
                        interpret: bool = False):
    """Fused-bag variant of `embedding_bag` for VMEM-resident tables.

    Grid (B/8,) instead of (B/8, bag): the table is placed in VMEM ONCE
    (no per-step DMA), and each grid step gathers + reduces 8 whole bags
    in-kernel from its block of ids in SMEM. bag x fewer grid steps, and each
    output block is written once instead of zero-init + bag
    read-modify-write revisits. Falls back to the row-DMA baseline when
    the table exceeds the VMEM budget or the bag exceeds the unroll
    bound."""
    v, d = table.shape
    bag = ids.shape[1]
    if (v * d * table.dtype.itemsize > _FUSED_MAX_TABLE_BYTES
            or bag > _FUSED_MAX_BAG):
        return embedding_bag(table, ids, combiner=combiner,
                             interpret=interpret)
    table, ids, b, win = _prepare(table, ids)
    bp = ids.shape[0]
    kernel = functools.partial(_fused_kernel, bag=bag, win=win,
                               combiner=combiner)
    out = pl.pallas_call(
        kernel,
        grid=(bp // _ROWS,),
        in_specs=[_ids_spec(bag), pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((_ROWS, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, d), jnp.float32),
        interpret=interpret,
    )(ids, table)
    return out[:b]
