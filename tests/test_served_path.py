"""The served training path on the CPU: how the pipeline starts its
workers next to a live JAX backend, the compile-cache rule of the entry
points, and the shared loop (`repro.train.feed_loop`) end to end at a
tiny width."""
import json
import math
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.common import compile_cache
from repro.configs.base import DLRMConfig
from repro.data import proc_executor
from repro.data.pipeline import StageGraph, StageSpec
from repro.data.proc_executor import ProcessPipeline
from repro.data.simulator import MachineSpec
from repro.train.feed_loop import DLRMTrainer, criteo_record, train_on_feed

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def test_workers_start_clean_once_jax_backend_is_up():
    """A parent holding a JAX backend (on the chip: the TPU) never forks
    its workers, and a worker never brings up a backend of its own: the
    source stage returns the worker's own `jax_backend_initialized()`,
    which a forked child would inherit as True."""
    jax.devices()
    assert proc_executor.jax_backend_initialized()
    spec = StageGraph("probe", (StageSpec("src", "source", cost=0.001,
                                          serial_frac=0.0,
                                          mem_per_worker_mb=4.0),),
                      batch_mb=1.0)
    pipe = ProcessPipeline(
        spec, fns={"src": proc_executor.jax_backend_initialized},
        machine=MachineSpec(n_cpus=1, mem_mb=4096.0), item_mb=1.0)
    try:
        assert pipe._ctx.get_start_method() != "fork"
        pipe.set_allocation([1], prefetch_mb=4.0)
        seen = [pipe.get_batch(timeout=60.0) for _ in range(3)]
    finally:
        acct = pipe.shutdown(drain=False)
    assert seen == [False] * 3
    assert acct["joined"] is True


_TRAINER_SCRIPT = """
import os, sys
if __name__ != "__main__":          # imported again in a child process
    open(os.environ["MAIN_IMPORTED"], "a").write(__name__ + "\\n")
import jax
from repro.data import proc_executor
from repro.data.pipeline import StageGraph, StageSpec
from repro.data.simulator import MachineSpec

if __name__ == "__main__":
    jax.devices()
    spec = StageGraph("probe", (StageSpec("src", "source", cost=0.001,
                                          serial_frac=0.0,
                                          mem_per_worker_mb=4.0),),
                      batch_mb=1.0)
    pipe = proc_executor.ProcessPipeline(
        spec, fns={"src": proc_executor.jax_backend_initialized},
        machine=MachineSpec(n_cpus=2, mem_mb=4096.0), item_mb=1.0)
    pipe.set_allocation([2], prefetch_mb=4.0)
    seen = [pipe.get_batch(timeout=60.0) for _ in range(4)]
    acct = pipe.shutdown(drain=False)
    print(pipe._ctx.get_start_method(), seen, acct["joined"],
          hasattr(sys.modules["__main__"], "__file__"))
"""


def test_trainer_script_is_not_imported_by_workers(tmp_path):
    """A trainer script that imports jax at its top and holds a backend:
    its workers start from the forkserver without importing the script
    again (Python would, outside "fork"), so no worker imports jax, and
    the script's own `__main__` is left as it was."""
    script = tmp_path / "trainer.py"
    script.write_text(_TRAINER_SCRIPT)
    marker = tmp_path / "imported"
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               MAIN_IMPORTED=str(marker))
    out = subprocess.run([sys.executable, str(script)], env=env, check=True,
                         capture_output=True, text=True, timeout=300)
    assert out.stdout.split("\n")[-2] == \
        "forkserver [False, False, False, False] True True"
    assert not marker.exists()


def _cache_dirs(env_dir):
    """(JAX's cache dir at start, what enable_compile_cache returns,
    JAX's cache dir after it) in a fresh interpreter."""
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = env_dir
    env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    code = ("import json, jax\n"
            "from repro.common.compile_cache import enable_compile_cache\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "used = enable_compile_cache()\n"
            "print(json.dumps([before, used, "
            "jax.config.jax_compilation_cache_dir]))\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=120)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("env_set", [True, False], ids=["set", "unset"])
def test_compile_cache_dir_rule(env_set, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX uses it and the entry
    point names no other; unset, the cache is the fixed `.jax_cache` in
    the checkout, set at program start and not on import."""
    env_dir = str(tmp_path / "cache") if env_set else None
    before, used, after = _cache_dirs(env_dir)
    if env_set:
        assert before == used == after == env_dir
    else:
        assert before is None
        assert used == after == compile_cache.DEFAULT_DIR
        assert used == os.path.join(os.path.dirname(SRC), ".jax_cache")


def test_train_on_feed_tiny_dlrm():
    """The shared loop end to end on the CPU: raw records through a tuned
    ProcessPipeline (forkserver workers: the trainer brought JAX up
    first), make_train_feed, FeedBackend + Session(InTune), train step.
    Every step's loss is finite, and `on_batch` sees each fed batch at
    the model's shapes."""
    cfg = DLRMConfig(name="dlrm-tiny", n_sparse=4, n_dense=13, embed_dim=16,
                     vocab_sizes=(512,) * 4, bottom_mlp=(32, 16),
                     top_mlp=(32, 1), multi_hot=1)
    trainer = DLRMTrainer(cfg, optimizer="rowwise_adagrad", lr=0.02)
    record = criteo_record(cfg, batch=64)
    shapes = []
    run = train_on_feed(
        trainer, record, steps=4, tune_every=2,
        machine=MachineSpec(n_cpus=2, mem_mb=4096),
        on_batch=lambda i, p, b: shapes.append(b["sparse_ids"].shape),
        log_every=0)
    assert len(run.losses) == 4
    assert all(math.isfinite(x) for x in run.losses)
    assert shapes == [(64, 4, 1)] * 4
    assert run.teardown["all_joined"] is True
    assert np.isfinite(run.step_time_s) and run.step_time_s > 0
