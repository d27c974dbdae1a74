"""Optimizers in pure JAX (no optax): SGD-momentum, Adam(W), Adagrad,
Adafactor.

Adafactor (factored second moments) is what makes the 1T-param kimi-k2
config fit HBM: per-matrix state is O(rows + cols) instead of O(rows*cols).
Adagrad is the classic DLRM embedding optimizer.

API:
    opt = make_optimizer("adam", lr=1e-3, ...)
    state = opt.init(params)
    params, state, stats = opt.update(grads, state, params, step)

`opt_logical_axes(name, params_logical)` returns the logical-axis tree for
the optimizer state so it shards exactly like the parameters it mirrors.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.common.pytree import global_norm


class Optimizer(NamedTuple):
    name: str
    init: Callable[[Any], Any]
    update: Callable[..., Any]   # (grads, state, params, step) -> (p, s, stats)


# Tensors bigger than this (elements) get their optimizer update scanned
# over axis 0 (layer-stacked weights): caps the f32 transient working set at
# one slice instead of the whole 5-GiB expert slab. Without this, the
# elementwise f32 chains (g32, g^2, u, p32) for the 1T-param configs
# dominate peak memory (observed ~40 GiB/device on kimi-k2 train).
_CHUNK_ELEMS = 1 << 26


def _chunked(fn, p, g, *states):
    """Apply fn(p_slice, g_slice, *state_slices) -> tuple, scanning over
    axis 0 for huge stacked tensors; otherwise apply directly."""
    if p.ndim < 3 or p.size <= _CHUNK_ELEMS:
        return fn(p, g, *states)

    def body(_, xs):
        return None, fn(*xs)
    _, out = jax.lax.scan(body, None, (p, g) + states)
    return out


# ------------------------------------------------------------ schedules ----
def warmup_cosine(base_lr: float, warmup: int, total: int,
                  min_frac: float = 0.1):
    def lr_at(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base_lr * step / max(warmup, 1)
        frac = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = base_lr * (min_frac + (1 - min_frac) * 0.5 *
                         (1 + jnp.cos(jnp.pi * frac)))
        return jnp.where(step < warmup, warm, cos)
    return lr_at


def constant_lr(base_lr: float):
    return lambda step: jnp.asarray(base_lr, jnp.float32)


# ------------------------------------------------------------- clipping ----
def clip_by_global_norm(grads, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), gn


# ------------------------------------------------------------------ sgd ----
def sgd(lr_fn, momentum: float = 0.9, grad_clip: float = 0.0):
    def init(params):
        return {"mu": jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def update(grads, state, params, step):
        gn = global_norm(grads)
        if grad_clip:
            grads, _ = clip_by_global_norm(grads, grad_clip)
        lr = lr_fn(step)
        mu = jax.tree_util.tree_map(
            lambda m, g: momentum * m + g.astype(jnp.float32),
            state["mu"], grads)
        new_p = jax.tree_util.tree_map(
            lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype),
            params, mu)
        return new_p, {"mu": mu}, {"lr": lr, "grad_norm": gn}
    return Optimizer("sgd", init, update)


# ----------------------------------------------------------------- adam ----
def adam(lr_fn, b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
         weight_decay: float = 0.0, grad_clip: float = 1.0,
         state_dtype=jnp.float32):
    """AdamW. state_dtype=bfloat16 halves state memory (documented loss of
    precision — a large-model knob, not the default)."""
    def init(params):
        z = lambda p: jnp.zeros(p.shape, state_dtype)
        return {"m": jax.tree_util.tree_map(z, params),
                "v": jax.tree_util.tree_map(z, params)}

    def update(grads, state, params, step):
        gn = global_norm(grads)
        if grad_clip:
            grads, _ = clip_by_global_norm(grads, grad_clip)
        lr = lr_fn(step)
        t = jnp.asarray(step, jnp.float32) + 1.0
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def leaf(p, g, m, v):
            g32 = g.astype(jnp.float32)
            m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
            v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
            step_ = lr * (m32 / bc1) / (jnp.sqrt(v32 / bc2) + eps)
            p32 = p.astype(jnp.float32)
            if weight_decay:
                step_ = step_ + lr * weight_decay * p32
            return ((p32 - step_).astype(p.dtype), m32.astype(state_dtype),
                    v32.astype(state_dtype))

        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = treedef.flatten_up_to(grads)
        m_leaves = treedef.flatten_up_to(state["m"])
        v_leaves = treedef.flatten_up_to(state["v"])
        new_p, new_m, new_v = [], [], []
        for p, g, m, v in zip(p_leaves, g_leaves, m_leaves, v_leaves):
            np_, nm, nv = _chunked(leaf, p, g, m, v)
            new_p.append(np_)
            new_m.append(nm)
            new_v.append(nv)
        unf = lambda leaves: jax.tree_util.tree_unflatten(treedef, leaves)
        return unf(new_p), {"m": unf(new_m), "v": unf(new_v)}, \
            {"lr": lr, "grad_norm": gn}
    return Optimizer("adam", init, update)


# -------------------------------------------------------------- adagrad ----
def adagrad(lr_fn, eps: float = 1e-10, grad_clip: float = 0.0):
    """Classic DLRM embedding optimizer."""
    def init(params):
        return {"acc": jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def update(grads, state, params, step):
        gn = global_norm(grads)
        if grad_clip:
            grads, _ = clip_by_global_norm(grads, grad_clip)
        lr = lr_fn(step)

        def leaf(p, g, a):
            g32 = g.astype(jnp.float32)
            a32 = a + jnp.square(g32)
            return ((p.astype(jnp.float32)
                     - lr * g32 / (jnp.sqrt(a32) + eps)).astype(p.dtype),
                    a32)

        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = treedef.flatten_up_to(grads)
        a_leaves = treedef.flatten_up_to(state["acc"])
        new_p, new_a = [], []
        for p, g, a in zip(p_leaves, g_leaves, a_leaves):
            np_, na = _chunked(leaf, p, g, a)
            new_p.append(np_)
            new_a.append(na)
        unf = lambda leaves: jax.tree_util.tree_unflatten(treedef, leaves)
        return unf(new_p), {"acc": unf(new_a)}, {"lr": lr, "grad_norm": gn}
    return Optimizer("adagrad", init, update)


# ------------------------------------------------------ rowwise adagrad ----
def rowwise_adagrad(lr_fn, eps: float = 1e-10,
                    rowwise_min_elems: int = 1 << 24):
    """FBGEMM-style row-wise Adagrad: embedding-table leaves (huge, >=2D)
    keep ONE accumulator scalar per row (mean of squared grads over the
    embedding dim) — 1/dim the state of elementwise adagrad, the standard
    DLRM memory trick. Small/dense leaves use elementwise adagrad."""
    def _rowwise(p):
        return p.ndim >= 2 and p.size > rowwise_min_elems

    def init(params):
        def per(p):
            shape = p.shape[:-1] if _rowwise(p) else p.shape
            return jnp.zeros(shape, jnp.float32)
        return {"acc": jax.tree_util.tree_map(per, params)}

    def update(grads, state, params, step):
        gn = global_norm(grads)
        lr = lr_fn(step)

        def leaf(p, g, a):
            g32 = g.astype(jnp.float32)
            if a.shape != p.shape:             # row-wise
                a32 = a + jnp.mean(jnp.square(g32), axis=-1)
                scale = jax.lax.rsqrt(a32 + eps)[..., None]
            else:
                a32 = a + jnp.square(g32)
                scale = jax.lax.rsqrt(a32 + eps)
            return (p.astype(jnp.float32) - lr * g32 * scale).astype(
                p.dtype), a32

        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = treedef.flatten_up_to(grads)
        a_leaves = treedef.flatten_up_to(state["acc"])
        new_p, new_a = [], []
        for p, g, a in zip(p_leaves, g_leaves, a_leaves):
            scope = "table_update" if a.shape != p.shape else "dense_update"
            with jax.named_scope(scope):
                np_, na = _chunked(leaf, p, g, a)
            new_p.append(np_)
            new_a.append(na)
        unf = lambda leaves: jax.tree_util.tree_unflatten(treedef, leaves)
        return unf(new_p), {"acc": unf(new_a)}, {"lr": lr, "grad_norm": gn}
    return Optimizer("rowwise_adagrad", init, update)


# ------------------------------------------------------------ adafactor ----
def adafactor(lr_fn, decay: float = 0.8, eps1: float = 1e-30,
              eps2: float = 1e-3, clip_threshold: float = 1.0,
              min_dim_factored: int = 128):
    """Adafactor (Shazeer & Stern 2018), factored for params with both of the
    last two dims >= min_dim_factored; small params keep a full 2nd moment.
    No first moment (momentum=0), matching the memory-lean configuration."""
    def _factored(p):
        return p.ndim >= 2 and p.shape[-1] >= min_dim_factored \
            and p.shape[-2] >= min_dim_factored

    def init(params):
        def per(p):
            if _factored(p):
                return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                        jnp.float32)}
            return {"v": jnp.zeros(p.shape, jnp.float32)}
        return {"f": jax.tree_util.tree_map(per, params)}

    # state leaves are dicts, so flatten against the params treedef.
    def update(grads, state, params, step):
        gn = global_norm(grads)
        lr = lr_fn(step)
        t = jnp.asarray(step, jnp.float32) + 1.0
        beta = 1.0 - t ** (-decay)

        def leaf_factored(p, g, vr_old, vc_old):
            g32 = g.astype(jnp.float32)
            g2 = jnp.square(g32) + eps1
            vr = beta * vr_old + (1 - beta) * jnp.mean(g2, axis=-1)
            vc = beta * vc_old + (1 - beta) * jnp.mean(g2, axis=-2)
            r = vr / jnp.maximum(jnp.mean(vr, axis=-1, keepdims=True), eps1)
            u = g32 / (jnp.sqrt(r)[..., None]
                       * jnp.sqrt(vc)[..., None, :] + eps1)
            # NOTE: under chunked updates, update-clipping RMS and the
            # param scale are per-layer-slice (a mild, documented variation)
            rms_u = jnp.sqrt(jnp.mean(jnp.square(u)) + eps1)
            u = u / jnp.maximum(1.0, rms_u / clip_threshold)
            p32 = p.astype(jnp.float32)
            scale = jnp.maximum(eps2, jnp.sqrt(jnp.mean(jnp.square(p32))))
            return (p32 - lr * scale * u).astype(p.dtype), vr, vc

        def leaf_full(p, g, v_old):
            g32 = g.astype(jnp.float32)
            g2 = jnp.square(g32) + eps1
            v = beta * v_old + (1 - beta) * g2
            u = g32 / (jnp.sqrt(v) + eps1)
            rms_u = jnp.sqrt(jnp.mean(jnp.square(u)) + eps1)
            u = u / jnp.maximum(1.0, rms_u / clip_threshold)
            p32 = p.astype(jnp.float32)
            scale = jnp.maximum(eps2, jnp.sqrt(jnp.mean(jnp.square(p32))))
            return (p32 - lr * scale * u).astype(p.dtype), v

        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        g_leaves = treedef.flatten_up_to(grads)
        s_leaves = treedef.flatten_up_to(state["f"])
        new_p, new_s = [], []
        for p, g, s in zip(p_leaves, g_leaves, s_leaves):
            if "vr" in s:
                np_, vr, vc = _chunked(leaf_factored, p, g, s["vr"], s["vc"])
                new_s.append({"vr": vr, "vc": vc})
            else:
                np_, v = _chunked(leaf_full, p, g, s["v"])
                new_s.append({"v": v})
            new_p.append(np_)
        return (jax.tree_util.tree_unflatten(treedef, new_p),
                {"f": jax.tree_util.tree_unflatten(treedef, new_s)},
                {"lr": lr, "grad_norm": gn})

    return Optimizer("adafactor", init, update)


# -------------------------------------------------------------- factory ----
def make_optimizer(name: str, *, lr: float = 1e-3, total_steps: int = 10000,
                   warmup: int = 100, **kw) -> Optimizer:
    lr_fn = warmup_cosine(lr, warmup, total_steps)
    if name == "sgd":
        return sgd(lr_fn, **kw)
    if name == "adam":
        return adam(lr_fn, **kw)
    if name == "adagrad":
        return adagrad(lr_fn, **kw)
    if name == "rowwise_adagrad":
        return rowwise_adagrad(lr_fn, **kw)
    if name == "adafactor":
        return adafactor(lr_fn, **kw)
    raise ValueError(f"unknown optimizer {name!r}")


def opt_logical_axes(name: str, params_logical, params=None,
                     min_dim_factored: int = 128):
    """Logical-axis tree for optimizer state, mirroring the params tree."""
    if name == "sgd":
        return {"mu": params_logical}
    if name == "adam":
        return {"m": params_logical, "v": params_logical}
    if name == "adagrad":
        return {"acc": params_logical}
    if name == "rowwise_adagrad":
        assert params is not None, "rowwise axes need param shapes"
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        lg_leaves = treedef.flatten_up_to(params_logical)
        out = []
        for p, lg in zip(p_leaves, lg_leaves):
            lg = tuple(lg)
            out.append(lg[:-1] if p.ndim >= 2 and p.size > (1 << 24)
                       else lg)
        return {"acc": jax.tree_util.tree_unflatten(treedef, out)}
    if name == "adafactor":
        assert params is not None, "adafactor axes need param shapes"
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        lg_leaves = treedef.flatten_up_to(params_logical)
        out = []
        for p, lg in zip(p_leaves, lg_leaves):
            lg = tuple(lg)
            if p.ndim >= 2 and p.shape[-1] >= min_dim_factored \
                    and p.shape[-2] >= min_dim_factored:
                out.append({"vr": lg[:-1], "vc": lg[:-2] + lg[-1:]})
            else:
                out.append({"v": lg})
        return {"f": jax.tree_util.tree_unflatten(treedef, out)}
    raise ValueError(name)
