"""End-to-end driver: train a ~100M-parameter DLRM for a few hundred steps
on synthetic Criteo data, with checkpoint/restart and the InTune
controller tuning ingestion alongside.

    PYTHONPATH=src python examples/train_dlrm_criteo.py [--steps 300]

Two backends:

  --backend proc (default)  THE CLOSED LOOP. A real ProcessPipeline runs
      the featurization stages (hashing / pooling / padding / collation,
      data/featurize.py) in worker processes; batches cross into jax
      through `device_feed.make_train_feed` (device_prefetch + stall
      metering); the InTune controller tunes THIS pipeline — the one the
      train step actually eats from — via `FeedBackend` + `Session.step`,
      observing measured `device_idle_frac` at the feed boundary.

  --backend sim  the legacy mode, kept for hosts where forking worker
      processes is unwanted. NOTE: in this mode the controller tunes a
      SIMULATED MachineSpec(n_cpus=128) pipeline that is completely
      DETACHED from the data actually fed to the model (batches are
      synthesized inline by CriteoStream); tuner output never changes
      what the train loop sees. It demonstrates the controller loop, not
      a closed tuning loop — use the default proc backend for that.

~100M params: 12 tables x 2^16 rows x 96-dim = 75.5M embedding, plus
bottom/top MLPs (kept modest so the CPU run finishes in minutes). The
proc loop is `repro.train.feed_loop.train_on_feed`; `chip_smoke.py`
runs the same loop on a TPU at dlrm-criteo's published widths.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.base import DLRMConfig
from repro.core.controller import InTune
from repro.data.featurize import RecordSpec
from repro.data.pipeline import criteo_pipeline
from repro.data.simulator import MachineSpec
from repro.data.synthetic import CriteoStream
from repro.train import checkpoint as ckpt
from repro.train.feed_loop import DLRMTrainer, train_on_feed


def build_trainer():
    n_sparse, rows, dim = 12, 1 << 16, 96
    cfg = DLRMConfig(
        name="dlrm-100m", n_sparse=n_sparse, n_dense=13,
        embed_dim=dim, vocab_sizes=(rows,) * n_sparse,
        bottom_mlp=(512, 256, 96), top_mlp=(1024, 512, 256, 1))
    trainer = DLRMTrainer(cfg, optimizer="adagrad", lr=0.02)
    print(f"model: {trainer.n_params/1e6:.1f}M params")
    return trainer


def restore_or_init(ckpt_dir, params, opt_state, tuner):
    start = 0
    last = ckpt.latest_step(ckpt_dir)
    if last is not None:
        tree, manifest = ckpt.restore(ckpt_dir)
        params, opt_state = tree["params"], tree["opt_state"]
        start = manifest["step"] + 1
        if "intune" in manifest["extras"]:
            ex = manifest["extras"]["intune"]
            tuner.load_state_dict({
                "agent": {"qnet": tree["intune_qnet"],
                          "steps": ex["agent_steps"]},
                "workers": ex["workers"],
                "prefetch_mb": ex["prefetch_mb"]})
        print(f"resumed from step {start - 1}")
    return start, params, opt_state


def save_step(ckpt_dir, i, params, opt_state, tuner):
    st = tuner.state_dict()
    ckpt.save(ckpt_dir, i,
              {"params": params, "opt_state": opt_state,
               "intune_qnet": st["agent"]["qnet"]},
              extras={"intune": {
                  "workers": st["workers"],
                  "prefetch_mb": st["prefetch_mb"],
                  "agent_steps": st["agent"]["steps"]}})


def run_proc(args):
    """The closed loop: tuned ProcessPipeline feeds the real train step
    (the shared loop in repro.train.feed_loop)."""
    trainer = build_trainer()
    cfg = trainer.cfg
    # 4-hot bags pooled from raw lists of up to 8 ids
    record = RecordSpec(batch=args.batch, n_sparse=cfg.n_sparse,
                        n_dense=cfg.n_dense, vocab=cfg.vocab_sizes[0])

    def save(i, params, opt_state, tuner):
        if (args.ckpt_every and (i + 1) % args.ckpt_every == 0) \
                or i == args.steps - 1:
            save_step(args.ckpt_dir, i, params, opt_state, tuner)

    # pin_cpus=1 leaves the host's remaining cores (if any) to the
    # trainer process; the tuner's CPU headroom is contention-real
    run = train_on_feed(
        trainer, record, steps=args.steps, tune_every=args.tune_every,
        finetune_ticks=args.finetune_ticks, pin_cpus=1,
        restore=lambda p, o, t: restore_or_init(args.ckpt_dir, p, o, t),
        on_step=save)
    print(f"feed teardown: {run.teardown}")
    print(f"final loss {np.mean(run.losses[-20:]):.4f} "
          f"(first-20 {np.mean(run.losses[:20]):.4f}); "
          f"checkpoints in {args.ckpt_dir}")


def run_sim(args):
    """Legacy mode: the tuner tunes a SIMULATED 128-CPU machine; the
    batches fed to the model come from an inline CriteoStream and are
    unaffected by anything the tuner decides."""
    trainer = build_trainer()
    cfg = trainer.cfg
    stream = CriteoStream(n_sparse=cfg.n_sparse, n_dense=cfg.n_dense,
                          vocab=cfg.vocab_sizes[0])
    tuner = InTune(criteo_pipeline(), MachineSpec(n_cpus=128), seed=0,
                   head="factored", finetune_ticks=150)
    start, trainer.params, trainer.opt_state = restore_or_init(
        args.ckpt_dir, trainer.params, trainer.opt_state, tuner)
    t0 = time.time()
    losses = []
    for i in range(start, args.steps):
        batch = stream.feature_udf(stream.raw_block(args.batch))
        metrics = trainer.step(i, {k: jnp.asarray(v)
                                   for k, v in batch.items()})
        # simulated-pipeline tuning in lockstep with training steps; the
        # closed-loop form is `--backend proc` (FeedBackend + Session.step)
        tuner.tick()
        losses.append(float(metrics["loss"]))
        if i % 25 == 0:
            rate = (i - start + 1) * args.batch / (time.time() - t0)
            print(f"step {i:4d} loss {losses[-1]:.4f} "
                  f"({rate:,.0f} samples/s) sim pipeline "
                  f"{tuner.history[-1]['throughput']:.1f} b/s")
        if (args.ckpt_every and (i + 1) % args.ckpt_every == 0) \
            or i == args.steps - 1:
            save_step(args.ckpt_dir, i, trainer.params, trainer.opt_state,
                      tuner)
    print(f"final loss {np.mean(losses[-20:]):.4f} "
          f"(first-20 {np.mean(losses[:20]):.4f}); "
          f"checkpoints in {args.ckpt_dir}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=2048)
    ap.add_argument("--backend", choices=("proc", "sim"), default="proc",
                    help="proc = tuned ProcessPipeline actually feeds the "
                         "train step (closed loop); sim = tuner runs "
                         "against a simulated machine DETACHED from the "
                         "inline data the model trains on")
    ap.add_argument("--tune-every", type=int, default=2,
                    help="proc backend: train steps per tuning tick")
    ap.add_argument("--finetune-ticks", type=int, default=90,
                    help="proc backend: InTune exploration budget before "
                         "it serves its incumbent best allocation")
    ap.add_argument("--ckpt-every", type=int, default=100,
                    help="checkpoint cadence in steps; 0 = final step only")
    ap.add_argument("--ckpt-dir", default="experiments/ckpt_dlrm")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.backend == "proc":
        run_proc(args)
    else:
        run_sim(args)


if __name__ == "__main__":
    main()
