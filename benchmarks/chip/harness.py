"""One run of one cell: set-up, the checked steps, the measured window,
then the comparison with the reference.

The window drives the program's own served loop,
`repro.train.feed_loop.train_on_feed`, unchanged: raw records -> tuned
ProcessPipeline -> make_train_feed -> FeedBackend + Session(InTune) ->
the jitted step. The harness acts only through the loop's hooks:

  restore   compiles the tuner's own programs, which the loop would
            otherwise first compile deep inside the window;
  on_batch / on_step
            keep the first `CHECKED` fed batches and the parameters
            they left, open the window after the last set-up step,
            time the feed waits, and close the window by raising
            `WindowClosed`, after which the loop's own `finally` tears
            the pipeline down.

The trainer's `step` is wrapped, with no added wait, to read the
optimizer's state after the run's first step (the warm-up's, on the
seed's weights), to keep the checked steps' losses and every window
step's loss (checked for finite values once the window has closed), and
to mark the end of each step's dispatch for the host spans.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import check
import layout
import procstat
import reference

CHECKED = 3          # fed steps the reference replays
WARM_STEPS = (0, 0, 1, 2)   # the trainer's warm_up: its step indices
TUNER_MEM_MB = 16384        # the tuner's memory budget for the workers


class WindowClosed(Exception):
    """Raised from a hook to end the loop once the window has closed."""


def arch(cfg: Dict):
    """The configuration's architecture (`archs/<model>.py`)."""
    return layout.arch(cfg["model"])


def make_trainer(cfg: Dict, devices: List):
    """The program's trainer for `cfg`, on one device or over the mesh
    the configuration states."""
    mesh = None
    if cfg.get("mesh"):
        axes = tuple(cfg["mesh"])
        shape = tuple(cfg["mesh"][a] for a in axes)
        mesh = jax.make_mesh(shape, axes, (jax.sharding.AxisType.Auto,) * len(axes),
                             devices=devices[:int(np.prod(shape))])
    return arch(cfg).make_trainer(cfg, mesh)


def put_weights(trainer, cfg: Dict, seed: int) -> None:
    """Replace the trainer's parameters and optimizer state with the
    benchmark's weights for `seed`, drawn on the device(s) in one jitted
    call, in the served dtype and layout."""
    p_sh = jax.tree_util.tree_map(lambda a: a.sharding, trainer.params)
    o_sh = jax.tree_util.tree_map(lambda a: a.sharding, trainer.opt_state)
    trainer.params = trainer.opt_state = None
    gc.collect()
    dtype = jnp.dtype(cfg["param_dtype"])
    init = arch(cfg).init_params
    trainer.params = jax.jit(lambda w: init(w, cfg, dtype),
                             out_shardings=p_sh, compiler_options=reference.EXACT)(
        reference.seed_words(seed))
    trainer.opt_state = jax.jit(trainer.opt.init, out_shardings=o_sh)(trainer.params)


def _grad_from_acc(acc0, acc1, params):
    """Per-leaf norm of the gradient the optimizer took in the step
    between two Adagrad states: the accumulators grew by g^2 (by the
    row's mean g^2 where one accumulator serves a row)."""
    out = {}
    flat0 = jax.tree_util.tree_flatten_with_path(acc0["acc"])[0]
    for (path, a0), a1, p in zip(flat0, jax.tree_util.tree_leaves(acc1["acc"]),
                                 jax.tree_util.tree_leaves(params)):
        s = jnp.sum(a1 - a0)
        if a0.shape != p.shape:
            s = s * p.shape[-1]
        out[reference.leaf_name(path)] = jnp.sqrt(s)
    return out


def _change(params, words, cfg):
    init = arch(cfg).init_params(words, cfg, jnp.dtype(cfg["param_dtype"]))
    return reference.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), params, init))


_GRAD_FN = jax.jit(_grad_from_acc, compiler_options=reference.EXACT)
_CHANGE_FNS: Dict[str, Any] = {}


def _change_fn(cfg: Dict):
    key = json.dumps(cfg, sort_keys=True)
    if key not in _CHANGE_FNS:
        _CHANGE_FNS[key] = jax.jit(lambda p, w: _change(p, w, cfg),
                                   compiler_options=reference.EXACT)
    return _CHANGE_FNS[key]


def warm_tuner(tuner) -> None:
    """Compile the tuner's programs at the shapes it will use: its Q
    network's greedy action and, once its replay holds a batch, its TD
    update. Results are dropped; the tuner's state is untouched."""
    agent = getattr(tuner, "agent", None)
    if agent is None:
        return
    from repro.core.agent import greedy_action
    c = agent.cfg
    obs = np.zeros((c.batch_size, c.obs_dim), np.float32)
    batch = (obs, np.zeros((c.batch_size, c.n_stages), np.int64),
             np.zeros((c.batch_size,), np.float32), obs,
             np.zeros((c.batch_size,), np.float32))
    out = agent.update(agent.params, agent.target, agent.mom,
                       *[jnp.asarray(b) for b in batch])
    float(out[2])
    greedy_action(agent.params, np.zeros(c.obs_dim, np.float32), c)


class _Spans:
    """Host spans on the profiler's clock, switched from the hooks."""

    def __init__(self, on: bool):
        self.on = on
        self._open: List[Any] = []

    def enter(self, name: str) -> None:
        if self.on:
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
            self._open.append(ann)

    def exit(self) -> None:
        if self.on and self._open:
            self._open.pop().__exit__(None, None, None)

    def switch(self, name: str) -> None:
        self.exit()
        self.enter(name)


class FedRun:
    """Hooks for one `train_on_feed` call (see the module docstring)."""

    def __init__(self, trainer, cell: Dict, seed: int, seconds: float,
                 trace_dir: Optional[str] = None):
        self.trainer = trainer
        self.cfg, self.traffic = cell["config"], cell["traffic"]
        self.seed, self.seconds, self.trace_dir = seed, seconds, trace_dir
        self.open_at = CHECKED                # its on_step opens the window
        self.every = int(self.traffic["tune_every"])
        self.words = reference.seed_words(seed)
        self.fed: List[Dict[str, np.ndarray]] = []
        self.losses: Dict[int, Any] = {}
        self.window_losses: List[Any] = []
        self.acc0 = trainer.opt_state         # as put_weights left it
        self.grad = self.change = None
        self.window = False
        self.t0 = self.t1 = None
        self.t_last = 0.0
        self.steps = 0
        self.feed_wait_s = 0.0
        self.nonfinite = 0
        self.cpu0 = self.cpu1 = None
        self.spans = _Spans(trace_dir is not None)
        self._change_fn = _change_fn(self.cfg)
        orig = trainer.step

        def step(i, batch):
            m = orig(i, batch)
            if self.acc0 is not None:        # the run's first step
                self.grad = _GRAD_FN(self.acc0, trainer.opt_state, trainer.params)
                self.acc0 = None
            if i < CHECKED:
                self.losses[i] = m["loss"]
            if self.window:
                self.window_losses.append(m["loss"])
                # the loop ticks the tuner after every `tune_every`-th step
                self.spans.switch("tuner_tick" if i % self.every == 0 else "host_loop")
            return m
        trainer.step = step
        self._orig_step = orig

    # ------------------------------------------------------------ hooks --
    def restore(self, params, opt_state, tuner):
        warm_tuner(tuner)
        return 0, params, opt_state

    def on_batch(self, i, params, batch):
        if self.window:
            self.feed_wait_s += time.perf_counter() - self.t_last
            self.spans.switch("step_dispatch")
        elif i < self.open_at:
            self.fed.append(jax.device_get(batch))
        elif i == self.open_at:
            self.change = self._change_fn(params, self.words)

    def on_step(self, i, params, opt_state, tuner):
        if i == self.open_at:
            self._open()
        elif self.window:
            self.steps += 1
            if time.perf_counter() - self.t0 >= self.seconds:
                self._close()
                raise WindowClosed
            self.spans.switch("feed_wait")
        self.t_last = time.perf_counter()

    # ----------------------------------------------------------- window --
    def _open(self):
        if self.seconds <= 0:
            raise WindowClosed
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.cpu0 = procstat.pipeline_cpu_s()
        jax.block_until_ready(self.trainer.params)
        self.t0 = time.perf_counter()
        self.window = True
        self.spans.enter("bench_window")
        self.spans.enter("feed_wait")

    def _close(self):
        jax.block_until_ready(self.trainer.params)
        self.t1 = time.perf_counter()
        self.window = False
        self.spans.exit()
        self.spans.exit()
        self.cpu1 = procstat.pipeline_cpu_s()
        if self.trace_dir is not None:
            jax.profiler.stop_trace()
        # every window step's loss, whether or not the loop read it; a
        # step that left none counts as not finite
        losses = np.asarray(jax.device_get(self.window_losses), np.float64)
        self.nonfinite = (self.steps - len(losses)
                          + int(np.count_nonzero(~np.isfinite(losses))))
        self.window_losses = []

    # ---------------------------------------------------------- results --
    def program_readings(self) -> Dict:
        grad, change, losses = jax.device_get((self.grad, self.change,
                                               self.losses))
        return {"loss": [float(losses[i]) for i in sorted(losses)],
                "grad": {k: float(v) for k, v in grad.items()},
                "change": {k: float(v) for k, v in change.items()}}

    def replay_steps(self, warm: Dict[str, np.ndarray]):
        """[(optimizer step, host batch)] as the program took them."""
        return ([(s, warm) for s in WARM_STEPS]
                + list(enumerate(self.fed)))

    def free(self):
        """Drop every device array of the program's run."""
        self.trainer.step = self._orig_step
        self.trainer.params = self.trainer.opt_state = None
        self.acc0 = None
        gc.collect()


def drive(trainer, cell: Dict, seed: int, seconds: float,
          trace_dir: Optional[str] = None) -> FedRun:
    """Weights from the seed, then the served loop through the checked
    steps and (seconds > 0) the window."""
    from repro.data.simulator import MachineSpec
    from repro.train.feed_loop import train_on_feed
    t = cell["traffic"]
    put_weights(trainer, cell["config"], seed)
    run = FedRun(trainer, cell, seed, seconds, trace_dir)
    # the tuner's worker budget: the host's usable cores less those kept
    # for the trainer
    cpus = len(os.sched_getaffinity(0)) - int(t["tuner_reserved_cpus"])
    machine = MachineSpec(n_cpus=max(1, cpus), mem_mb=TUNER_MEM_MB)
    try:
        train_on_feed(trainer, arch(cell["config"]).record_spec(cell["config"], t, seed),
                      steps=1 << 40, tune_every=int(t["tune_every"]),
                      finetune_ticks=int(t["finetune_ticks"]), machine=machine,
                      restore=run.restore,
                      on_batch=run.on_batch, on_step=run.on_step, log_every=0)
    except WindowClosed:
        pass
    finally:
        # the workers are joined; end, and wait for, the forkserver that
        # started them (it starts again on demand)
        from multiprocessing import forkserver
        forkserver._forkserver._stop()
    return run


def stop_tracker() -> None:
    """End, and wait for, multiprocessing's resource tracker, once the
    pipeline's objects are gone (it starts again on demand)."""
    from multiprocessing import resource_tracker
    gc.collect()
    resource_tracker._resource_tracker._stop()


def reference_readings(run: FedRun, cell: Dict, *, device=None,
                       quant: str = "float32", fault: str = "") -> Dict:
    from repro.train.feed_loop import warm_batch
    cfg = cell["config"]
    model = arch(cfg)
    warm = warm_batch(model.record_spec(cfg, cell["traffic"], run.seed))
    shards = int(np.prod(list(cfg["mesh"].values()))) if cfg.get("mesh") else 1
    return model.replay(run.seed, cfg, run.replay_steps(warm), quant=quant,
                        fault=fault, shards=shards, device=device)


def compared(run: FedRun, program: Dict, ref: Dict) -> Dict[str, float]:
    """Every number `correct` compares, by name."""
    numbers = check.training_gaps(program, ref, CHECKED)
    numbers["feed_faults"] = float(len(arch(run.cfg).feed_faults(run.fed, run.cfg)))
    numbers["window_nonfinite"] = float(run.nonfinite)
    return numbers
