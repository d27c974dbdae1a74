"""jit'd public wrappers for the Pallas kernels.

The kernels are compiled for the TPU by default. Off the chip, callers
pass `interpret=True` explicitly (the kernel body then executes via the
Pallas interpreter — how correctness is validated on a CPU host); a
compiled call on a backend that is not a TPU raises instead of quietly
interpreting.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import dot_interact as _di
from repro.kernels import embedding_bag as _eb
from repro.kernels import sage_aggregate as _sa


@functools.partial(jax.jit, static_argnames=("combiner", "interpret"))
def embedding_bag(table, ids, *, combiner: str = "sum",
                  interpret: bool = False):
    return _eb.embedding_bag(table, ids, combiner=combiner,
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("combiner", "interpret"))
def embedding_bag_fused(table, ids, *, combiner: str = "sum",
                        interpret: bool = False):
    """Perf variant: whole-bag reduction per grid step (bag x fewer grid
    steps than `embedding_bag`, bit-identical results)."""
    return _eb.embedding_bag_fused(table, ids, combiner=combiner,
                                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def dot_interact(feats, *, tile_b: int = 128, interpret: bool = False):
    return _di.dot_interact(feats, tile_b=tile_b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def sage_aggregate(neigh, w, *, tile_b: int = 128, interpret: bool = False):
    return _sa.sage_aggregate(neigh, w, tile_b=tile_b, interpret=interpret)
