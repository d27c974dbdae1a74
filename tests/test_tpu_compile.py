"""Compiles of the main path for a described (unattached) TPU v5e chip.

Interpret mode cannot see what the chip's compiler refuses: blocks that
are not tile-aligned, more VMEM than a kernel may use, a program larger
than the chip's memory. These tests compile, without a chip, the train
step of dlrm-criteo at one chip's share, the DLRM-DCNv2 train step at
its benchmark cell's shapes, and the Pallas kernels at the widths the
dlrm-criteo step uses (D = 128, F = 27). Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only the worker that runs this file loads the TPU
compiler.
"""
import json
import math
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import DLRMConfig
from repro.configs.dlrm_criteo import ARCH, ONE_CHIP
from repro.kernels import ops
from repro.models import dlrm as dlrm_lib
from repro.train.optim import make_optimizer
from repro.train.train_step import make_train_step

V5E_HBM_BYTES = 16 * 10**9      # one v5e chip: 16 GB of HBM
DCNV2_CELL = (pathlib.Path(__file__).resolve().parents[1]
              / "benchmarks/chip/configs/dlrm-dcnv2-1chip.json")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def test_train_step_fits_one_chip(one_chip):
    """dlrm-criteo at one chip's share (2^19 rows x 26 tables, bf16,
    rowwise Adagrad, batch 65,536): the whole step program fits one
    chip's HBM."""
    cfg = ONE_CHIP
    batch = ARCH.shape("train_batch").batch
    opt = make_optimizer(ARCH.optimizer, lr=0.02)
    params = jax.eval_shape(
        lambda: dlrm_lib.init_params(jax.random.PRNGKey(0), cfg)[0])
    opt_state = jax.eval_shape(opt.init, params)
    data = {"sparse_ids": jax.ShapeDtypeStruct(
                (batch, cfg.n_sparse, cfg.multi_hot), jnp.int32),
            "dense": jax.ShapeDtypeStruct((batch, cfg.n_dense), jnp.float32),
            "label": jax.ShapeDtypeStruct((batch,), jnp.float32)}
    step = jax.jit(make_train_step(
        lambda p, b: dlrm_lib.loss_fn(p, cfg, b), opt))
    compiled = step.lower(
        _on(one_chip, params), _on(one_chip, opt_state),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        _on(one_chip, data)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    tables = cfg.n_sparse * cfg.vocab_sizes[0] * cfg.embed_dim * 2
    assert mem.argument_size_in_bytes >= tables
    assert total < V5E_HBM_BYTES, (total, mem)


def _entry_ops(hlo: str):
    """(name, opcode, elements of its largest output array, metadata) of
    each instruction of the ENTRY computation of compiled HLO text: the
    passes the step makes, fused computations left out."""
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    inst = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\(")
    for line in entry.splitlines()[1:]:
        m = inst.match(line)
        if m is None:
            continue
        sizes = [math.prod(int(d) for d in dims.split(",") if d)
                 for dims in re.findall(r"\[([\d,]*)\]", m.group(2))]
        yield m.group(1), m.group(3), max(sizes, default=1), line[m.end():]


def _relayouts(hlo: str, elements: int):
    """The ENTRY instructions under scope `bag_lookup` with at least
    `elements` elements out that only move data: copies, pads, reshapes
    that are not bitcasts, selects, or fusions of them."""
    moves = ("copy", "pad", "reshape", "select")
    return [(name, op) for name, op, n, rest in _entry_ops(hlo)
            if "bag_lookup" in rest and n >= elements
            and (op in moves or (op == "fusion"
                                 and any(w in name for w in moves)))]


def test_dcnv2_bag_lookup_keeps_its_rows_in_place(one_chip):
    """The DLRM-DCNv2 train step at its benchmark cell's shapes (26
    packed tables, 214 ids an example, batch 16,384, float32): the bag
    lookup gathers its rows into the layout the bags' sum reads, and its
    gradient into the layout the scatter reads, without a further pass
    over the (batch, 214, 128) rows; the temporaries stay under 2 GB."""
    cell = json.loads(DCNV2_CELL.read_text())
    cfg = DLRMConfig(
        name=cell["name"], n_sparse=len(cell["rows"]), n_dense=cell["n_dense"],
        embed_dim=cell["embed_dim"], vocab_sizes=tuple(cell["rows"]),
        hot_sizes=tuple(cell["hot_sizes"]), bottom_mlp=tuple(cell["bottom_mlp"]),
        top_mlp=tuple(cell["top_mlp"]), interaction="dcn",
        dcn_layers=cell["dcn_layers"], dcn_rank=cell["dcn_rank"],
        param_dtype=cell["param_dtype"])
    batch, slots = cell["batch"], sum(cfg.hots)
    opt = make_optimizer(cell["optimizer"], lr=cell["lr"])
    params = jax.eval_shape(
        lambda: dlrm_lib.init_params(jax.random.PRNGKey(0), cfg)[0])
    data = {"sparse_ids": jax.ShapeDtypeStruct((batch, slots), jnp.int32),
            "dense": jax.ShapeDtypeStruct((batch, cfg.n_dense), jnp.float32),
            "label": jax.ShapeDtypeStruct((batch,), jnp.float32)}
    step = jax.jit(make_train_step(
        lambda p, b: dlrm_lib.loss_fn(p, cfg, b), opt))
    compiled = step.lower(
        _on(one_chip, params), _on(one_chip, jax.eval_shape(opt.init, params)),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        _on(one_chip, data)).compile()
    hlo = compiled.as_text()
    assert any("bag_lookup" in rest for *_, rest in _entry_ops(hlo))
    assert _relayouts(hlo, batch * slots * cfg.embed_dim) == []
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.0e9


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("kernel,rows,bag", [
    ("embedding_bag", 1 << 19, 1),          # one chip's table share
    ("embedding_bag", 1 << 19, 4),
    ("embedding_bag_fused", 16384, 1),      # VMEM-resident table
    ("embedding_bag_fused", 16384, 4),
])
def test_embedding_bag_compiles(one_chip, kernel, rows, bag, dtype):
    """Both embedding-bag kernels compile for the chip at D = 128 into a
    Mosaic custom call (no (1, D) block, tile-aligned bf16 windows)."""
    fn = getattr(ops, kernel)
    table = jax.ShapeDtypeStruct((rows, 128), dtype, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((65536, bag), jnp.int32, sharding=one_chip)
    compiled = fn.lower(table, ids).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_embedding_bag_fused_table_budget_compiles(one_chip):
    """The largest table the fused variant keeps in VMEM (its 8 MiB
    budget, bf16 at D = 128) still compiles."""
    from repro.kernels import embedding_bag as eb
    rows = eb._FUSED_MAX_TABLE_BYTES // (128 * 2)
    table = jax.ShapeDtypeStruct((rows, 128), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((4096, 2), jnp.int32, sharding=one_chip)
    compiled = ops.embedding_bag_fused.lower(table, ids).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_dot_interact_compiles(one_chip, dtype):
    """The DLRM interaction kernel at F = 27 (26 tables + the bottom MLP
    output), D = 128."""
    feats = jax.ShapeDtypeStruct((65536, 27, 128), dtype, sharding=one_chip)
    compiled = ops.dot_interact.lower(feats).compile()
    assert "tpu_custom_call" in compiled.as_text()
