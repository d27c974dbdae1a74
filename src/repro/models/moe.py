"""Mixture-of-Experts FFN with shard_map expert parallelism.

Design (see DESIGN.md §5): activations arrive sharded over the data axes and
*replicated* over the model axis; experts are sharded over the model axis.
Each model-rank routes (replicated, cheap), dispatches only the token-choices
destined to ITS local experts via a sort→gather formulation (no giant GShard
dispatch-mask einsum, no scatter in the forward), runs the expert GEMMs as a
batched einsum, combines with a scatter-add into its partial output, and one
psum over the model axis completes the block — the same single all-reduce a
Megatron TP MLP costs. Shared experts are tensor-parallel over the same axis
and fused into the same psum.

Capacity semantics: per-expert capacity C = ceil(T_local * top_k * cf / E)
(rounded up to a multiple of 8); token-choices beyond capacity are dropped
(GShard-style), their combine weight never applied.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import activation

P = jax.sharding.PartitionSpec


def capacity_for(t_local: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(-(-t_local * top_k * cf // n_experts))
    return max(8, -(-c // 8) * 8)


def init_moe_params(rng, n_layers, d_model, n_experts_padded, d_expert,
                    n_shared, dtype):
    """Stacked-over-layers MoE params + logical-axis tree."""
    k = jax.random.split(rng, 7)
    e, d, f = n_experts_padded, d_model, d_expert
    s = lambda *sh: sh
    params = {
        "router": jax.random.normal(k[0], (n_layers, d, e), jnp.float32) * d ** -0.5,
        "wi": jax.random.normal(k[1], s(n_layers, e, d, f), dtype) * d ** -0.5,
        "wg": jax.random.normal(k[2], s(n_layers, e, d, f), dtype) * d ** -0.5,
        "wo": jax.random.normal(k[3], s(n_layers, e, f, d), dtype) * f ** -0.5,
    }
    logical = {
        "router": ("layers", "embed", None),
        # expert dim -> model (EP); d_model dim -> fsdp (ZeRO-3 storage,
        # gathered per layer by the shard_map in_specs reshard)
        "wi": ("layers", "expert", "fsdp", "expert_mlp"),
        "wg": ("layers", "expert", "fsdp", "expert_mlp"),
        "wo": ("layers", "expert", "expert_mlp", "fsdp"),
    }
    if n_shared:
        fs = n_shared * d_expert
        params["shared"] = {
            "wi": jax.random.normal(k[4], (n_layers, d, fs), dtype) * d ** -0.5,
            "wg": jax.random.normal(k[5], (n_layers, d, fs), dtype) * d ** -0.5,
            "wo": jax.random.normal(k[6], (n_layers, fs, d), dtype) * fs ** -0.5,
        }
        logical["shared"] = {
            "wi": ("layers", "fsdp", "mlp"),
            "wg": ("layers", "fsdp", "mlp"),
            "wo": ("layers", "mlp", "fsdp"),
        }
    return params, logical


def _route(x, router_w, n_experts: int, top_k: int, norm_topk: bool):
    """Router in fp32. Padded experts (cols >= n_experts) get -inf logits."""
    logits = x.astype(jnp.float32) @ router_w  # (T, E_pad)
    e_pad = router_w.shape[-1]
    if e_pad > n_experts:
        pad_mask = jnp.arange(e_pad) >= n_experts
        logits = jnp.where(pad_mask, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, top_k)  # (T, k)
    if norm_topk:
        topw = topw / jnp.maximum(jnp.sum(topw, -1, keepdims=True), 1e-9)
    return probs, topw, topi


def _aux_loss(probs, topi, n_experts: int):
    """Switch-style load-balance loss over the real (unpadded) experts."""
    t, k = topi.shape
    hits = jnp.zeros((probs.shape[-1],), jnp.float32).at[topi.reshape(-1)].add(1.0)
    frac_routed = hits[:n_experts] / (t * k)
    frac_prob = jnp.mean(probs[:, :n_experts], axis=0)
    return n_experts * jnp.sum(frac_routed * frac_prob)


def _dispatch_local(x, flat_e, flat_w, e_start, e_loc: int, cap: int):
    """Sort→gather dispatch of token-choices to this rank's local experts.

    Returns xbuf (e_loc, cap, D), wbuf (e_loc, cap), tok (e_loc, cap).
    Pure gathers in the forward (backward is a scatter-add, which XLA
    partitions fine since indices are rank-local).
    """
    tk = flat_e.shape[0]
    tok_of = jnp.arange(tk) // (tk // x.shape[0])
    local_e = jnp.where(
        (flat_e >= e_start) & (flat_e < e_start + e_loc),
        flat_e - e_start, e_loc)                       # e_loc == overflow bin
    order = jnp.argsort(local_e)                        # stable: groups experts
    counts = jnp.zeros((e_loc + 1,), jnp.int32).at[local_e].add(1)[:e_loc]
    starts = jnp.cumsum(counts) - counts                # exclusive
    slot_c = jnp.arange(cap)
    src = starts[:, None] + slot_c[None, :]             # (e_loc, cap)
    valid = slot_c[None, :] < jnp.minimum(counts, cap)[:, None]
    entry = order[jnp.minimum(src, tk - 1)]             # (e_loc, cap)
    tok = tok_of[entry]
    xbuf = x[tok] * valid[..., None].astype(x.dtype)
    wbuf = jnp.where(valid, flat_w[entry], 0.0)
    return xbuf, wbuf, tok


def _moe_local(x, p, *, cfg, e_start, e_loc: int, tp_axis: Optional[str],
               dp_axes: Tuple[str, ...]):
    """Per-device MoE block. x: (T_local, D). Returns (y, aux_loss)."""
    t, d = x.shape
    act = activation(cfg.act)
    probs, topw, topi = _route(x, p["router"], cfg.n_experts, cfg.top_k,
                               cfg.norm_topk_prob)
    aux = _aux_loss(probs, topi, cfg.n_experts)
    cap = capacity_for(t, cfg.top_k, max(cfg.n_experts, 1), cfg.capacity_factor)
    xbuf, wbuf, tok = _dispatch_local(
        x, topi.reshape(-1), topw.reshape(-1).astype(x.dtype), e_start, e_loc, cap)
    # Expert GEMMs: (e, c, d) x (e, d, f) -> (e, c, f)
    h = act(jnp.einsum("ecd,edf->ecf", xbuf, p["wg"])) * \
        jnp.einsum("ecd,edf->ecf", xbuf, p["wi"])
    out = jnp.einsum("ecf,efd->ecd", h, p["wo"])        # (e, c, d)
    out = out * wbuf[..., None]
    y = jnp.zeros((t, d), x.dtype).at[tok.reshape(-1)].add(
        out.reshape(-1, d))
    if "shared" in p:  # tensor-parallel shared experts, fused into same psum
        hs = act(x @ p["shared"]["wg"]) * (x @ p["shared"]["wi"])
        y = y + hs @ p["shared"]["wo"]
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    if dp_axes:
        aux = jax.lax.pmean(aux, dp_axes)
    return y, aux


def moe_ffn(x, p, cfg, mesh: Optional[jax.sharding.Mesh], e_pad: int):
    """MoE FFN over tokens x: (B, S, D) or (T, D). Returns (y, aux)."""
    orig_shape = x.shape
    x2 = x.reshape(-1, x.shape[-1])
    t = x2.shape[0]

    if mesh is None or cfg.moe_impl == "local":
        y, aux = _moe_local(x2, p, cfg=cfg, e_start=0, e_loc=e_pad,
                            tp_axis=None, dp_axes=())
        return y.reshape(orig_shape), aux

    names = mesh.axis_names
    tp_axis = "model" if "model" in names else None
    tp = mesh.shape.get("model", 1) if tp_axis else 1
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    if t % max(dp, 1) != 0:  # e.g. decode batch 1: replicate over data
        dp_axes, dp = (), 1
    assert e_pad % max(tp, 1) == 0, (e_pad, tp)
    e_loc = e_pad // max(tp, 1)

    x_spec = P(dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None), None)
    w_specs = {
        "router": P(None, None),
        "wi": P("model" if tp_axis else None, None, None),
        "wg": P("model" if tp_axis else None, None, None),
        "wo": P("model" if tp_axis else None, None, None),
    }
    if "shared" in p:
        w_specs["shared"] = {
            "wi": P(None, "model" if tp_axis else None),
            "wg": P(None, "model" if tp_axis else None),
            "wo": P("model" if tp_axis else None, None),
        }

    def fn(xl, pl):
        e_start = (jax.lax.axis_index(tp_axis) * e_loc) if tp_axis and tp > 1 \
            else 0
        return _moe_local(xl, pl, cfg=cfg, e_start=e_start, e_loc=e_loc,
                          tp_axis=tp_axis if tp > 1 else None,
                          dp_axes=dp_axes)

    y, aux = jax.shard_map(
        fn, mesh=mesh, in_specs=(x_spec, w_specs),
        out_specs=(x_spec, P()), check_vma=False)(x2, p)
    return y.reshape(orig_shape), aux
