"""The served training path: raw records -> tuned `ProcessPipeline` ->
`device_feed.make_train_feed` -> `FeedBackend` + `Session` with `InTune`
-> jitted train step.

One loop, shared by `examples/train_dlrm_criteo.py` (long CPU runs with
checkpoint/restart) and `chip_smoke.py` (a few steps at the published
widths on the chip, checked against a float32 reference):

  - `DLRMTrainer` holds the parameters, the optimizer state and the
    compiled train step of one DLRM config, on one device or with the
    tables row-sharded over a mesh (`forward(ctx=...)` ->
    `tp_multifeature_bag`);
  - `train_on_feed` runs the closed loop: the pipeline's worker
    processes featurize raw click records, batches cross onto the device
    through the metered feed, and InTune retunes the pipeline between
    train steps against the measured feed telemetry.

The pipeline is created only after the model is on the device, so its
workers start from a clean forkserver (`proc_executor.default_context`),
never forked from the process that holds the chip. InTune starts
untrained: no run reads a pretrained agent from disk.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import shardlib
from repro.configs.base import DLRMConfig
from repro.data.featurize import RecordSpec, featurize_block, raw_block
from repro.data.simulator import Allocation, MachineSpec
from repro.launch.programs import abstract_init, shardings_for
from repro.models import dlrm as dlrm_lib
from repro.train.optim import make_optimizer, opt_logical_axes
from repro.train.train_step import make_train_step


def criteo_record(cfg: DLRMConfig, batch: int, seed: int = 0) -> RecordSpec:
    """Click records for `cfg`: Criteo's categorical fields are single
    values, so each raw list holds `multi_hot` ids and pools to
    `multi_hot`."""
    return RecordSpec(batch=batch, n_sparse=cfg.n_sparse, n_dense=cfg.n_dense,
                      vocab=cfg.vocab_sizes[0], k_raw=cfg.multi_hot,
                      hot=cfg.multi_hot, seed=seed)


def warm_batch(record: RecordSpec) -> Dict[str, np.ndarray]:
    """A fixed model-ready batch (the pipeline's own featurization over
    records drawn from seed 0) for compiling and warming the step."""
    return featurize_block(raw_block(np.random.RandomState(0), record),
                           record)


class DLRMTrainer:
    """Parameters, optimizer state and train step of one DLRM config.

    With `mesh` None everything lives on the default device. With a mesh
    (axes "data" and "model"), the config's sharding rules place the
    tables row-sharded over the mesh and the batch split over "data";
    the step then looks rows up through `tp_multifeature_bag`. Parameters
    are drawn from `seed` with JAX's partitionable PRNG, so a sharded and
    a one-device trainer start from identical values."""

    def __init__(self, cfg: DLRMConfig, *, optimizer: str = "rowwise_adagrad",
                 lr: float = 0.02, seed: int = 0,
                 mesh: Optional[Mesh] = None) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.opt = make_optimizer(optimizer, lr=lr)
        init = lambda: dlrm_lib.init_params(jax.random.PRNGKey(seed), cfg)
        abs_params, logical = abstract_init(init)
        if mesh is None:
            ctx = None
            p_shard = o_shard = self.batch_sharding = None
        else:
            rules = shardlib.make_rules(dict(cfg.sharding_overrides))
            ctx = shardlib.ShardCtx(mesh, rules)
            p_shard = shardings_for(abs_params, logical, rules, mesh)
            abs_opt = jax.eval_shape(self.opt.init, abs_params)
            o_shard = shardings_for(abs_opt, opt_logical_axes(
                optimizer, logical, params=abs_params), rules, mesh)
            self.batch_sharding = NamedSharding(mesh, P("data"))
        self.params = jax.jit(lambda: init()[0], out_shardings=p_shard)()
        self.opt_state = jax.jit(self.opt.init,
                                 out_shardings=o_shard)(self.params)
        self.loss_fn = lambda p, b: dlrm_lib.loss_fn(p, cfg, b, ctx=ctx)
        step = make_train_step(self.loss_fn, self.opt)
        if mesh is None:
            self._step = jax.jit(step)
        else:
            self._step = jax.jit(
                step, in_shardings=(p_shard, o_shard,
                                    NamedSharding(mesh, P()),
                                    self.batch_sharding),
                out_shardings=(p_shard, o_shard, None))
        self.forward = jax.jit(
            lambda p, b: dlrm_lib.forward(p, cfg, b, ctx=ctx))
        self.compile_s: Optional[float] = None

    @property
    def n_params(self) -> int:
        return sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(self.params))

    def put(self, batch: Dict[str, Any]) -> Dict[str, jax.Array]:
        """A host batch onto the device(s) the step expects it on."""
        if self.batch_sharding is None:
            return {k: jnp.asarray(v) for k, v in batch.items()}
        return {k: jax.device_put(v, self.batch_sharding)
                for k, v in batch.items()}

    def compile(self, batch: Dict[str, jax.Array]) -> float:
        """Trace, lower and compile the step for this batch's shapes;
        returns the seconds it took (later calls reuse the executable)."""
        t0 = time.monotonic()
        self._step = self._step.lower(self.params, self.opt_state, 0,
                                      batch).compile()
        self.compile_s = time.monotonic() - t0
        return self.compile_s

    def step(self, i: int, batch: Dict[str, jax.Array]) -> Dict[str, Any]:
        self.params, self.opt_state, metrics = self._step(
            self.params, self.opt_state, i, batch)
        return metrics

    def warm_up(self, batch: Dict[str, jax.Array], iters: int = 3) -> float:
        """Compile (if needed), then time `iters` steps on `batch`: the
        uncontended device step time, host clock around
        `block_until_ready`."""
        if self.compile_s is None:
            self.compile(batch)
        self.step(0, batch)
        jax.block_until_ready(self.params)
        t0 = time.monotonic()
        for k in range(iters):
            self.step(k, batch)
        jax.block_until_ready(self.params)
        return (time.monotonic() - t0) / iters


@dataclasses.dataclass
class FeedRun:
    """What one `train_on_feed` call measured. Times are host-clock
    seconds; `examples_per_s` is wall-clock over the stepped window, not
    a device metric."""
    losses: List[float]
    step_time_s: float          # warm, uncontended step (block_until_ready)
    examples_per_s: float       # over the steps after the first
    workers: List[int]
    teardown: Dict[str, Any]


def train_on_feed(trainer: DLRMTrainer, record: RecordSpec, *, steps: int,
                  tune_every: int = 2, finetune_ticks: int = 90,
                  machine: Optional[MachineSpec] = None,
                  pin_cpus: Optional[int] = None,
                  restore: Optional[Callable] = None,
                  on_batch: Optional[Callable] = None,
                  on_step: Optional[Callable] = None,
                  log_every: int = 25) -> FeedRun:
    """Train `trainer` for `steps` steps on batches a tuned
    ProcessPipeline featurizes from raw `record`s.

    Hooks (all optional):
      restore(params, opt_state, tuner) -> (start, params, opt_state)
          resume from a checkpoint before the first step;
      on_batch(i, params, batch)   before step i, with the device batch;
      on_step(i, params, opt_state, tuner)   after step i.
    """
    from repro.api import FeedBackend, Session
    from repro.core.controller import InTune
    from repro.data.device_feed import make_train_feed
    from repro.data.featurize import featurize_stage_fns
    from repro.data.pipeline import train_feed_pipeline
    from repro.data.proc_executor import ProcessPipeline

    # warm the step and measure its uncontended device time: the
    # pipeline's CPU budget (train_feed_pipeline cpu_share) is set
    # relative to THIS, so ingestion can keep up at a sane allocation
    # but not at a bad one
    step_time = trainer.warm_up(trainer.put(warm_batch(record)))
    print(f"measured device step time: {step_time * 1e3:.1f} ms")

    spec = train_feed_pipeline(step_time_s=step_time, work="real")
    # n_cpus bounds how far the tuner's exploration can over-place
    # workers: every extra worker steals silicon from the trainer itself
    machine = machine if machine is not None \
        else MachineSpec(n_cpus=12, mem_mb=4096)
    pipe = ProcessPipeline(spec, fns=featurize_stage_fns(spec, record=record),
                           machine=machine, pin_cpus=pin_cpus)
    pipe.set_allocation([1] * len(spec.stages), prefetch_mb=32.0)
    # timeout: a cold pipeline must push one batch through every stage
    # serially before anything reaches the sink
    feed = make_train_feed(pipe, depth=2, sharding=trainer.batch_sharding,
                           timeout=max(120.0, 60.0 * step_time))
    # device_step_s: on a shared-core host ingestion steals silicon from
    # the trainer instead of letting it block, so device_idle_frac is
    # scored as 1 - device_busy/wall against the uncontended step time
    backend = FeedBackend(pipe, feed, device_step_s=step_time)
    # init_alloc: start the exploration walk where the pipe actually
    # launched (minimal workers), not at heuristic_even — at a feed
    # boundary the reward is device business, and over-placed workers
    # steal the trainer's own cores
    tuner = InTune(spec, machine, seed=0, head="factored",
                   finetune_ticks=finetune_ticks,
                   init_alloc=Allocation(np.ones(len(spec.stages), dtype=int),
                                         prefetch_mb=32.0),
                   # live windows are noisy: visit-penalized incumbent
                   # tracking + switch hysteresis (see fig_train_feed)
                   lcb_coef=0.15, switch_margin=0.05)
    session = Session(backend, tuner)

    start = 0
    if restore is not None:
        start, trainer.params, trainer.opt_state = restore(
            trainer.params, trainer.opt_state, tuner)
    losses: List[float] = []
    t0 = time.monotonic()
    t_first = None          # after the first loss: pipeline cold start over
    try:
        for i in range(start, steps):
            batch = next(feed)
            if on_batch is not None:
                on_batch(i, trainer.params, batch)
            metrics = trainer.step(i, batch)
            losses.append(float(metrics["loss"]))
            if t_first is None:
                t_first = time.monotonic()
            if i % tune_every == 0:
                jax.block_until_ready(trainer.params)  # close the window
                session.step()
            if log_every and i % log_every == 0:
                rate = len(losses) * record.batch / (time.monotonic() - t0)
                print(f"step {i:4d} loss {losses[-1]:.4f} "
                      f"({rate:,.0f} examples/s wall-clock) "
                      f"workers {pipe.worker_counts()}")
            if on_step is not None:
                on_step(i, trainer.params, trainer.opt_state, tuner)
        t_end = time.monotonic()
        workers = list(pipe.worker_counts())
    finally:
        acct = session.close()
    warm = (len(losses) - 1) * record.batch / (t_end - t_first) \
        if len(losses) > 1 else 0.0
    return FeedRun(losses=losses, step_time_s=step_time,
                   examples_per_s=warm, workers=workers, teardown=acct)
