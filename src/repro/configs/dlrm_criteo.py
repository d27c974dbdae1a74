"""dlrm-criteo — the paper's own Criteo workload (InTune §5, Meta DLRM).

26 sparse + 13 dense Criteo features, embed_dim=128, bottom MLP
512-256-128, top MLP 1024-1024-512-256-1. Rows hashed to 2^23 per table:
26 * 8,388,608 * 128 ≈ 27.9B embedding params — the paper's "25B+
parameters, most of which are in the embedding tables". Trained with
hybrid parallelism (tables row-sharded over `model`), optimizer adagrad.
Not one of the 40 assigned cells — an extra row in the dry-run matrix.

`ONE_CHIP` is the deployment one chip of a 16-chip row-sharded job
holds: every published width kept, one-hot lookups, and only the rows of
each table cut to that chip's shard, 2^23 / 16 = 2^19 (`REDUCED` lists
the cut). 26 * 2^19 * 128 bf16 = 3.49 GB of tables; the train step
(tables, their dense gradient, the new tables) fits one 16 GB v5e.
"""
from repro.configs.base import ArchSpec, DLRMConfig, RECSYS_SHAPES

PUBLISHED_ROWS = 1 << 23
CHIPS_PER_JOB = 16

MODEL = DLRMConfig(
    name="dlrm-criteo",
    n_sparse=26, n_dense=13, embed_dim=128,
    vocab_sizes=(PUBLISHED_ROWS,) * 26,
    bottom_mlp=(512, 256, 128),
    top_mlp=(1024, 1024, 512, 256, 1),
    multi_hot=1,
    # bf16 tables + shard_map row-wise lookup; row-wise adagrad below. The
    # paper-faithful fp32/adagrad/GSPMD baseline is variant 0 in
    # benchmarks/perf_hillclimb.
    param_dtype="bfloat16",
    tp_lookup=True,
    # 27.9B embedding params need every mesh axis:
    # 2^23 rows / 512 devices = 16384 rows per shard.
    sharding_overrides=(("table_rows", ("pod", "data", "model")),),
)

ONE_CHIP = MODEL.replace(
    vocab_sizes=(PUBLISHED_ROWS // CHIPS_PER_JOB,) * MODEL.n_sparse)

# every field ONE_CHIP changes: (field, published, one chip's share)
REDUCED = (("vocab_sizes", MODEL.vocab_sizes[0], ONE_CHIP.vocab_sizes[0]),)

ARCH = ArchSpec(
    arch_id="dlrm-criteo", family="dlrm", model=MODEL, shapes=RECSYS_SHAPES,
    source="InTune paper §5 / arXiv:1906.00091", optimizer="rowwise_adagrad",
)
