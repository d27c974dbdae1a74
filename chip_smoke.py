"""Chip smoke test: train dlrm-criteo on the served path for a few steps.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # tables row-sharded over four chips

One chip: dlrm-criteo at its published widths (only the rows of each
table cut to one chip's share of a 16-chip job, `configs/dlrm_criteo`
ONE_CHIP) trains through raw records -> tuned ProcessPipeline ->
make_train_feed -> FeedBackend + Session(InTune) -> jitted train step
(`repro.train.feed_loop`). It checks that every loss is finite and that
the device forward on the first fed batch agrees with a plain float32
forward of the same parameters.

Four chips (`--chips 4`, only this phase): the same loop with the tables
row-sharded over a (data=2, model=2) mesh, fed through
make_train_feed(sharding=...); the fed batches are kept and replayed on
one chip with the same initial parameters, and the two runs' losses and
final logits must agree.

The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed. Without a TPU the script
exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs.dlrm_criteo import ARCH, ONE_CHIP, REDUCED
from repro.data.simulator import MachineSpec
from repro.train.feed_loop import (DLRMTrainer, criteo_record,
                                   train_on_feed, warm_batch)

# The device forward keeps parameters and activations in bf16, which
# holds 8 significant bits (relative rounding <= 2^-9 ~ 0.2%). It rounds
# after each of its 8 dense layers and the dot interaction, and the last
# layer sums 256 mixed-sign terms into a logit much smaller than its
# inputs, so the rounding grows relative to the logit: a bf16 forward on
# the CPU differs from float32 by up to 1.4% of max|logit| at these widths.
# The bound leaves 3.5x room for the chip's own bf16 matmul rounding.
REF_TOL = 0.05          # max |device - float32| over max |float32 logit|
# Sharded vs one chip: the same math in another reduction order. On the
# same initial parameters the sharded forward (MLP contractions split
# over "model", bf16 partial products added, row-shard partial lookups
# psum'd) rounds apart from one chip's by about as much as bf16 differs
# from float32 (REF_TOL). Over the steps, the elementwise Adagrad of the
# MLPs turns tiny gradient differences near zero into whole-size update
# differences, so the float32 mean losses drift apart: 1.1e-3 relative
# after 4+6 steps on four virtual CPU devices. The losses barely see a
# wrong table gradient (Adagrad normalises each row's step), which is
# why the gradient is checked on its own below.
SHARD_LOSS_RTOL = 5e-3
SHARD_LOGIT_TOL = REF_TOL  # max |sharded - one chip| over max |logit|
# Table gradients: the bf16 backward rounds the cotangent at every layer,
# so each layout's table gradient sits some way from the float32 one (in
# the L2 norm over the looked-up rows), and on the chip the two layouts
# differ from each other by 10% (CHANGES.md). Each is therefore held to
# the float32 gradient: the sharded one may sit at most twice as far from
# it as one chip's (or 2%, where both are closer). A sharded backward
# that lost, doubled or misplaced a row's contributions sits far outside.
SHARD_GRAD_RATIO = 2.0
SHARD_GRAD_FLOOR = 0.02


def require_tpu(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX reports "
                 f"{devices[0].platform!r} devices); nothing was run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"JAX reports {len(devices)}")
    return devices


def lookup_rows(tables, ids):
    """tables (F, V, D), ids (B, F, hot) -> the looked-up rows (B, F, hot, D)."""
    return tables[jnp.arange(ids.shape[1])[None, :, None], ids]


def reference_logits(params, batch, rows=None):
    """Plain float32 DLRM forward, written apart from the model code:
    bottom MLP, one-hot/multi-hot table sums, lower-triangle pairwise
    dots, top MLP. Rows are gathered in bf16 and then upcast, which is
    exactly the upcast table's rows; `rows` (B, F, hot, D) stands in for
    the lookup."""
    f32 = lambda x: x.astype(jnp.float32)
    if rows is None:
        rows = lookup_rows(params["tables"], batch["sparse_ids"])
    with jax.default_matmul_precision("highest"):
        x = f32(batch["dense"])
        for layer in params["bottom"]:
            x = jax.nn.relu(x @ f32(layer["w"]) + f32(layer["b"]))
        f = rows.shape[1]
        feats = jnp.concatenate([x[:, None], f32(rows).sum(2)], axis=1)
        gram = jnp.einsum("bfd,bgd->bfg", feats, feats)
        ii, jj = np.tril_indices(f + 1, k=-1)
        z = jnp.concatenate([gram[:, ii, jj], x], axis=-1)
        top = params["top"]
        for k, layer in enumerate(top):
            z = z @ f32(layer["w"]) + f32(layer["b"])
            if k < len(top) - 1:
                z = jax.nn.relu(z)
        return z[:, 0]


def table_grad_rows(trainer, batch):
    """d loss / d tables at the rows `batch` looks up, (B, F, hot, D):
    where the sharded backward scatters each row's gradient."""

    def rows(params, batch):
        g = jax.grad(lambda p: trainer.loss_fn(p, batch)[0])(params)
        return lookup_rows(g["tables"], batch["sparse_ids"])
    return jax.jit(rows)(trainer.params, batch)


@jax.jit
def reference_grad_rows(params, batch):
    """The float32 counterpart of `table_grad_rows`: the gradient of the
    reference forward's mean log loss with respect to each looked-up row,
    summed over the repeats of a (feature, id) as a table gradient sums
    them. Never builds the float32 table or its dense gradient."""
    ids = batch["sparse_ids"]

    def loss(rows):
        z = reference_logits(params, batch, rows)
        y = batch["label"]
        return jnp.mean(jnp.maximum(z, 0) - z * y
                        + jnp.log1p(jnp.exp(-jnp.abs(z))))
    rows = lookup_rows(params["tables"], ids).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        g = jax.grad(loss)(rows)
    keys = (jnp.arange(ids.shape[1])[None, :, None] * params["tables"].shape[1]
            + ids).reshape(-1)
    _, inv = jnp.unique(keys, return_inverse=True, size=keys.size)
    inv = inv.reshape(-1)
    summed = jax.ops.segment_sum(g.reshape(-1, g.shape[-1]), inv,
                                 num_segments=keys.size)
    return summed[inv].reshape(g.shape)


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def print_config(cfg, reduced, batch):
    print(f"config: {cfg.name} n_sparse={cfg.n_sparse} n_dense={cfg.n_dense} "
          f"embed_dim={cfg.embed_dim} bottom_mlp={cfg.bottom_mlp} "
          f"top_mlp={cfg.top_mlp} multi_hot={cfg.multi_hot} "
          f"param_dtype={cfg.param_dtype} rows/table={cfg.vocab_sizes[0]}")
    for field, published, used in reduced:
        print(f"reduced: {field} {published} -> {used} per table")
    print(f"batch: {batch}; host cores: {os.cpu_count()} "
          f"(usable {len(os.sched_getaffinity(0))})")


def one_chip(args, device):
    cfg = ONE_CHIP
    print_config(cfg, REDUCED, args.batch)
    record = criteo_record(cfg, args.batch, seed=args.seed)
    trainer = DLRMTrainer(cfg, optimizer=ARCH.optimizer, lr=args.lr,
                          seed=args.seed)
    print(f"params: {trainer.n_params:,} ({ARCH.optimizer})")
    compile_s = trainer.compile(trainer.put(warm_batch(record)))
    print(f"compile: train step {compile_s:.1f} s")

    check = {}
    ref_fn = jax.jit(reference_logits)

    def on_batch(i, params, batch):
        if check:
            return
        dev = np.asarray(trainer.forward(params, batch), np.float32)
        ref = np.asarray(ref_fn(params, batch))
        err = np.abs(dev - ref)
        scale = float(np.abs(ref).max())
        check.update(max_err=float(err.max()), scale=scale,
                     rel=float(err.max()) / scale,
                     rms_err=float(np.sqrt(np.mean(err ** 2))))

    cores = len(os.sched_getaffinity(0))
    run = train_on_feed(
        trainer, record, steps=args.steps, tune_every=2, finetune_ticks=90,
        machine=MachineSpec(n_cpus=max(1, cores - 1), mem_mb=16384),
        on_batch=on_batch, log_every=1)
    print(f"losses: {[round(x, 6) for x in run.losses]}")
    print(f"reference: max|device - float32| {check['max_err']:.3e} over "
          f"max|logit| {check['scale']:.3e} = {check['rel']:.4f} "
          f"(tolerance {REF_TOL}); rms error {check['rms_err']:.3e}")
    print(f"device step (warm, uncontended, host clock): "
          f"{run.step_time_s * 1e3:.1f} ms")
    print(f"throughput: {run.examples_per_s:,.0f} examples/s wall-clock "
          f"(host clock over steps 2..{len(run.losses)}, not a device "
          f"metric); pipeline workers {run.workers}")
    print(f"peak_bytes_in_use: {peak_bytes(device)}")
    print(f"feed teardown: {run.teardown}")
    if len(run.losses) != args.steps or \
            not all(math.isfinite(x) for x in run.losses):
        raise SystemExit(f"chip_smoke: losses not finite: {run.losses}")
    if not check or not check["rel"] <= REF_TOL:
        raise SystemExit(f"chip_smoke: device forward disagrees with the "
                         f"float32 reference: {check}")


def four_chips(args, devices):
    cfg = ONE_CHIP                 # same total rows as one chip holds
    print_config(cfg, REDUCED, args.batch)
    record = criteo_record(cfg, args.batch, seed=args.seed)
    # Auto axes: GSPMD propagates the shardings the config's rules place
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         (jax.sharding.AxisType.Auto,) * 2,
                         devices=devices[:4])
    print(f"mesh: {dict(mesh.shape)}; tables {cfg.vocab_sizes[0]} rows "
          f"row-sharded 4 ways, batch split 2 ways")
    sharded = DLRMTrainer(cfg, optimizer=ARCH.optimizer, lr=args.lr,
                          seed=args.seed, mesh=mesh)
    tables = sharded.params["tables"]
    print(f"tables sharding: {tables.sharding.spec}; per-device shard "
          f"{tables.addressable_shards[0].data.shape} on "
          f"{sorted(d.id for d in tables.sharding.device_set)}")
    warm = warm_batch(record)
    # the untrained forward and table gradient of both layouts, on
    # identical parameters
    logits_sharded = np.asarray(
        sharded.forward(sharded.params, sharded.put(warm)), np.float32)
    grads_sharded = np.asarray(table_grad_rows(sharded, sharded.put(warm)),
                               np.float32)
    print(f"compile: sharded train step "
          f"{sharded.compile(sharded.put(warm)):.1f} s")
    fed = []
    run = train_on_feed(
        sharded, record, steps=args.steps, tune_every=2,
        machine=MachineSpec(n_cpus=max(1, len(os.sched_getaffinity(0)) - 1),
                            mem_mb=16384),
        on_batch=lambda i, p, b: fed.append(jax.device_get(b)), log_every=1)
    print(f"sharded losses: {[round(x, 6) for x in run.losses]}")
    del sharded, tables
    gc.collect()

    # one chip, same initial parameters, same warm-up, same fed batches
    single = DLRMTrainer(cfg, optimizer=ARCH.optimizer, lr=args.lr,
                         seed=args.seed)
    logits_single = np.asarray(
        single.forward(single.params, single.put(warm)), np.float32)
    grads_single = np.asarray(table_grad_rows(single, single.put(warm)),
                              np.float32)
    grads_ref = np.asarray(reference_grad_rows(single.params,
                                               single.put(warm)))
    print(f"compile: one-chip train step "
          f"{single.compile(single.put(warm)):.1f} s")
    single.warm_up(single.put(warm))
    losses = [float(single.step(i, single.put(b))["loss"])
              for i, b in enumerate(fed)]
    print(f"one-chip losses: {[round(x, 6) for x in losses]}")
    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(run.losses, losses))
    logit_rel = float(np.abs(logits_sharded - logits_single).max()
                      / np.abs(logits_single).max())
    rel = lambda a, b: float(np.linalg.norm(a - b) / np.linalg.norm(b))
    grad_single, grad_sharded = (rel(grads_single, grads_ref),
                                 rel(grads_sharded, grads_ref))
    grad_tol = max(SHARD_GRAD_RATIO * grad_single, SHARD_GRAD_FLOOR)
    print(f"agreement: untrained forward max diff over max|logit| "
          f"{logit_rel:.3e} (tolerance {SHARD_LOGIT_TOL}); losses over "
          f"{len(losses)} fed steps max rel diff {loss_rel:.3e} (tolerance "
          f"{SHARD_LOSS_RTOL})")
    print(f"table gradient rows |diff|/|float32|: one chip "
          f"{grad_single:.3e}, sharded {grad_sharded:.3e} (tolerance "
          f"{grad_tol:.3e}); sharded vs one chip "
          f"{rel(grads_sharded, grads_single):.3e}")
    print(f"feed teardown: {run.teardown}")
    if not all(math.isfinite(x) for x in run.losses + losses):
        raise SystemExit("chip_smoke: losses not finite")
    if not (loss_rel <= SHARD_LOSS_RTOL and logit_rel <= SHARD_LOGIT_TOL
            and grad_sharded <= grad_tol):
        raise SystemExit("chip_smoke: sharded run disagrees with one chip")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--steps", type=int, default=8,
                    help="fed train steps after the warm-up steps")
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    print(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(args, devices)
    else:
        one_chip(args, devices[0])
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))


if __name__ == "__main__":
    main()
