"""Generic train/eval step builders.

make_train_step wires loss -> grad -> optimizer into a single jit-able
function; microbatching (gradient accumulation via lax.scan) is built in —
the memory knob the §Perf hillclimbs use on the train_4k cells.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.train.optim import Optimizer


def make_train_step(loss_fn: Callable, optimizer: Optimizer,
                    microbatches: int = 1):
    """loss_fn(params, batch) -> (loss, metrics dict).

    Returns train_step(params, opt_state, step, batch) ->
    (params, opt_state, metrics). With microbatches > 1, the batch's leading
    axis is split and gradients averaged via a scan (activation memory drops
    ~linearly; the optimizer still sees one global step).
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def one(params, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        return loss, metrics, grads

    def train_step(params, opt_state, step, batch):
        if microbatches == 1:
            loss, metrics, grads = one(params, batch)
        else:
            def split(x):
                b = x.shape[0]
                assert b % microbatches == 0, (b, microbatches)
                return x.reshape((microbatches, b // microbatches)
                                 + x.shape[1:])
            mb = jax.tree_util.tree_map(split, batch)

            def body(acc, mbatch):
                loss, metrics, grads = one(params, mbatch)
                acc_g, acc_l = acc
                acc_g = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32) / microbatches,
                    acc_g, grads)
                return (acc_g, acc_l + loss / microbatches), metrics

            zero_g = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), metrics_stack = jax.lax.scan(
                body, (zero_g, jnp.zeros((), jnp.float32)), mb)
            metrics = jax.tree_util.tree_map(jnp.mean, metrics_stack)

        with jax.named_scope("optimizer_update"):
            new_params, new_opt, stats = optimizer.update(
                grads, opt_state, params, step)
        out = dict(metrics)
        out.update(stats)
        out["loss"] = loss
        return new_params, new_opt, out

    return train_step


def make_eval_step(loss_fn: Callable):
    def eval_step(params, batch):
        loss, metrics = loss_fn(params, batch)
        out = dict(metrics)
        out["loss"] = loss
        return out
    return eval_step
