"""The four assigned recsys architectures.

  wide-deep  [1606.07792]  embedding concat -> deep MLP, + wide linear arm
  xdeepfm    [1803.05170]  CIN (compressed interaction network) + DNN + linear
  dien       [1809.03672]  GRU interest extractor + AUGRU interest evolution
  bert4rec   [1904.06690]  bidirectional self-attn over item sequences

Input conventions (produced by data/synthetic.py and launch/input_specs):
  CTR models (wide-deep, xdeepfm):
     sparse_ids (B, n_sparse, multi_hot) int32 hashed, dense (B, n_dense) f32,
     label (B,) f32
  dien:   hist_ids (B, S) int32, hist_mask (B, S) f32, target_id (B,) int32,
          dense (B, n_dense) f32, label (B,)
  bert4rec: item_seq (B, S) int32 (MASK = n_items), labels (B, S) int32
          (-1 = unmasked position)
Retrieval: score_candidates(user_inputs, cand_ids (C,)) -> (C,) scores.

Embedding tables are stacked (F, V, D), row-sharded over `model`
(models/embedding.py). All MLPs are plain fp32/bf16 dense stacks.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.configs.base import RecsysConfig
from repro.models.embedding import multifeature_bag


# ------------------------------------------------------------- mlp utils ---
def init_mlp(rng, dims: Sequence[int], dtype, final_bias=True):
    layers, logical = [], []
    keys = jax.random.split(rng, len(dims) - 1)
    for i in range(len(dims) - 1):
        layers.append({
            "w": jax.random.normal(keys[i], (dims[i], dims[i + 1]), dtype)
            * dims[i] ** -0.5,
            "b": jnp.zeros((dims[i + 1],), dtype),
        })
        logical.append({"w": ("fsdp", "mlp"), "b": ("mlp",)})
    return tuple(layers), tuple(logical)


def apply_mlp(layers, x, act=jax.nn.relu, final_act=False):
    for i, l in enumerate(layers):
        x = x @ l["w"] + l["b"]
        if final_act or i < len(layers) - 1:
            x = act(x)
    return x


def bce_loss(logit, label):
    logit = logit.astype(jnp.float32)
    loss = jnp.maximum(logit, 0) - logit * label + jnp.log1p(
        jnp.exp(-jnp.abs(logit)))
    return jnp.mean(loss)


# =============================================================== wide-deep ==
def init_wide_deep(rng, cfg: RecsysConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k = jax.random.split(rng, 4)
    rows = cfg.vocab_sizes[0]
    tables = jax.random.normal(
        k[0], (cfg.n_sparse, rows, cfg.embed_dim), dtype) * cfg.embed_dim ** -0.5
    wide = jax.random.normal(k[1], (cfg.n_sparse, rows), dtype) * 0.01
    deep_in = cfg.n_sparse * cfg.embed_dim + cfg.n_dense
    mlp, mlp_lg = init_mlp(k[2], (deep_in,) + cfg.mlp_dims + (1,), dtype)
    wide_dense = jax.random.normal(k[3], (cfg.n_dense, 1), dtype) * 0.01
    params = {"tables": tables, "wide": wide, "wide_dense": wide_dense,
              "mlp": mlp, "bias": jnp.zeros((), dtype)}
    logical = {"tables": (None, "table_rows", "table_dim"),
               "wide": (None, "table_rows"),
               "wide_dense": (None, None),
               "mlp": mlp_lg, "bias": ()}
    return params, logical


def _ctr_tables_lookup(params, cfg, batch, ctx):
    if cfg.tp_lookup and ctx is not None and ctx.mesh is not None:
        from repro.models.embedding import tp_multifeature_bag
        return tp_multifeature_bag(params["tables"], batch["sparse_ids"],
                                   ctx.mesh)
    return multifeature_bag(params["tables"], batch["sparse_ids"])


def wide_deep_forward(params, cfg: RecsysConfig, batch, ctx=None, emb=None):
    if emb is None:
        emb = _ctr_tables_lookup(params, cfg, batch, ctx)          # (B,F,D)
    deep_in = jnp.concatenate(
        [emb.reshape(emb.shape[0], -1),
         batch["dense"].astype(emb.dtype)], axis=-1)
    deep_logit = apply_mlp(params["mlp"], deep_in)[:, 0]
    # wide arm: per-feature scalar weights, multi-hot summed
    wide_w = jax.vmap(lambda t, i: jnp.take(t, i, axis=0),
                      in_axes=(0, 1), out_axes=1)(
        params["wide"], batch["sparse_ids"])          # (B, F, hot)
    wide_logit = jnp.sum(wide_w, axis=(1, 2)) + \
        (batch["dense"].astype(wide_w.dtype) @ params["wide_dense"])[:, 0]
    return deep_logit + wide_logit + params["bias"]


# ================================================================= xdeepfm ==
def init_xdeepfm(rng, cfg: RecsysConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k = jax.random.split(rng, 5)
    rows = cfg.vocab_sizes[0]
    tables = jax.random.normal(
        k[0], (cfg.n_sparse, rows, cfg.embed_dim), dtype) * cfg.embed_dim ** -0.5
    linear = jax.random.normal(k[1], (cfg.n_sparse, rows), dtype) * 0.01
    # CIN filters: layer k maps (H_{k-1} x m) interactions -> H_k maps
    cin, cin_lg = [], []
    h_prev, m = cfg.n_sparse, cfg.n_sparse
    kc = jax.random.split(k[2], len(cfg.cin_dims))
    for i, h in enumerate(cfg.cin_dims):
        cin.append(jax.random.normal(kc[i], (h, h_prev, m), dtype)
                   * (h_prev * m) ** -0.5)
        cin_lg.append(("mlp", None, None))
        h_prev = h
    dnn_in = cfg.n_sparse * cfg.embed_dim + cfg.n_dense
    dnn, dnn_lg = init_mlp(k[3], (dnn_in,) + cfg.mlp_dims + (1,), dtype)
    out_w = jax.random.normal(
        k[4], (sum(cfg.cin_dims), 1), dtype) * sum(cfg.cin_dims) ** -0.5
    params = {"tables": tables, "linear": linear, "cin": tuple(cin),
              "dnn": dnn, "cin_out": out_w, "bias": jnp.zeros((), dtype)}
    logical = {"tables": (None, "table_rows", "table_dim"),
               "linear": (None, "table_rows"),
               "cin": tuple(cin_lg), "dnn": dnn_lg,
               "cin_out": (None, None), "bias": ()}
    return params, logical


def xdeepfm_forward(params, cfg: RecsysConfig, batch, ctx=None, emb=None):
    x0 = emb if emb is not None else \
        _ctr_tables_lookup(params, cfg, batch, ctx)               # (B,m,D)
    # The 200 CIN filters don't divide the 16-way model axis, so the model
    # axis contributes NOTHING to the CIN under pure propagation — GSPMD
    # replicates the whole interaction network 16x (measured useful ratio
    # 0.06). Re-shard the CIN's batch over every axis instead (two ~40 MB
    # reshards around the block buy a 16x compute-parallelism win, §Perf 5).
    x0c = x0 if ctx is None else ctx.cs(x0, "act_all_batch", None, None)
    xk = x0c
    pooled = []
    for w in params["cin"]:
        # x_k[b,h,d] = sum_{i,j} W[h,i,j] * x_{k-1}[b,i,d] * x0[b,j,d],
        # associated as (contract i, then j).
        u = jnp.einsum("hij,bid->bhjd", w, xk)
        xk = jnp.einsum("bhjd,bjd->bhd", u, x0c)
        if ctx is not None:
            xk = ctx.cs(xk, "act_all_batch", None, None)
        pooled.append(jnp.sum(xk, axis=-1))                       # (B, H_k)
    cin_logit = (jnp.concatenate(pooled, axis=-1) @ params["cin_out"])[:, 0]
    dnn_in = jnp.concatenate(
        [x0.reshape(x0.shape[0], -1), batch["dense"].astype(x0.dtype)], -1)
    dnn_logit = apply_mlp(params["dnn"], dnn_in)[:, 0]
    lin_w = jax.vmap(lambda t, i: jnp.take(t, i, axis=0),
                     in_axes=(0, 1), out_axes=1)(
        params["linear"], batch["sparse_ids"])
    lin_logit = jnp.sum(lin_w, axis=(1, 2))
    return cin_logit + dnn_logit + lin_logit + params["bias"]


# ==================================================================== dien ==
def _gru_init(rng, d_in, d_h, dtype):
    k1, k2 = jax.random.split(rng)
    return {"w": jax.random.normal(k1, (d_in, 3 * d_h), dtype) * d_in ** -0.5,
            "u": jax.random.normal(k2, (d_h, 3 * d_h), dtype) * d_h ** -0.5,
            "b": jnp.zeros((3 * d_h,), dtype)}


def _gru_cell(p, x, h, a=None):
    """Standard GRU cell; if `a` (B,) given, AUGRU: update gate scaled by a.

    Gate order along the 3h axis: reset, update, candidate.
    """
    d_h = h.shape[-1]
    xw = x @ p["w"] + p["b"]
    hu = h @ p["u"]
    r = jax.nn.sigmoid(xw[..., :d_h] + hu[..., :d_h])
    z = jax.nn.sigmoid(xw[..., d_h:2 * d_h] + hu[..., d_h:2 * d_h])
    n = jnp.tanh(xw[..., 2 * d_h:] + r * hu[..., 2 * d_h:])
    if a is not None:
        z = z * a[:, None]
    return (1 - z) * h + z * n


def init_dien(rng, cfg: RecsysConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    k = jax.random.split(rng, 6)
    rows = cfg.vocab_sizes[0]
    item_table = jax.random.normal(
        k[0], (rows, cfg.embed_dim), dtype) * cfg.embed_dim ** -0.5
    gru1 = _gru_init(k[1], cfg.embed_dim, cfg.gru_dim, dtype)
    gru2 = _gru_init(k[2], cfg.gru_dim, cfg.gru_dim, dtype)
    att_w = jax.random.normal(
        k[3], (cfg.gru_dim, cfg.embed_dim), dtype) * cfg.gru_dim ** -0.5
    mlp_in = cfg.gru_dim + cfg.embed_dim + cfg.n_dense
    mlp, mlp_lg = init_mlp(k[4], (mlp_in,) + cfg.mlp_dims + (1,), dtype)
    params = {"items": item_table, "gru1": gru1, "gru2": gru2,
              "att_w": att_w, "mlp": mlp}
    g_lg = {"w": ("fsdp", "mlp"), "u": ("fsdp", "mlp"), "b": ("mlp",)}
    logical = {"items": ("table_rows", "table_dim"), "gru1": g_lg,
               "gru2": g_lg, "att_w": (None, None), "mlp": mlp_lg}
    return params, logical


def dien_interest_states(params, hist_emb):
    """First GRU pass (target-independent). hist_emb: (B, S, D) -> (B, S, H)."""
    b = hist_emb.shape[0]
    h0 = jnp.zeros((b, params["gru1"]["u"].shape[0]), hist_emb.dtype)

    def step(h, x_t):
        h = _gru_cell(params["gru1"], x_t, h)
        return h, h
    _, states = jax.lax.scan(step, h0, jnp.swapaxes(hist_emb, 0, 1))
    return jnp.swapaxes(states, 0, 1)                             # (B, S, H)


def dien_evolve(params, states, target_emb, hist_mask):
    """Attention + AUGRU second pass. Returns final interest (B, H)."""
    scores = jnp.einsum("bsh,hd,bd->bs", states, params["att_w"], target_emb)
    scores = jnp.where(hist_mask > 0, scores, -1e30)
    att = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(
        states.dtype)                                             # (B, S)
    b = states.shape[0]
    h0 = jnp.zeros((b, params["gru2"]["u"].shape[0]), states.dtype)

    def step(h, xs):
        s_t, a_t = xs
        return _gru_cell(params["gru2"], s_t, h, a=a_t), None
    h_final, _ = jax.lax.scan(
        step, h0, (jnp.swapaxes(states, 0, 1), jnp.swapaxes(att, 0, 1)))
    return h_final


def dien_forward(params, cfg: RecsysConfig, batch, ctx=None):
    hist = jnp.take(params["items"], batch["hist_ids"], axis=0)   # (B,S,D)
    hist = hist * batch["hist_mask"][..., None].astype(hist.dtype)
    target = jnp.take(params["items"], batch["target_id"], axis=0)
    states = dien_interest_states(params, hist)
    interest = dien_evolve(params, states, target, batch["hist_mask"])
    feats = jnp.concatenate(
        [interest, target, batch["dense"].astype(interest.dtype)], -1)
    return apply_mlp(params["mlp"], feats)[:, 0]


# ================================================================ bert4rec ==
def init_bert4rec(rng, cfg: RecsysConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    d, hN = cfg.embed_dim, cfg.n_heads
    k = jax.random.split(rng, 3 + cfg.n_blocks)
    # +MASK, +PAD, then pad to a multiple of 16 so the row-sharded table
    # divides the model axis (extra rows are never referenced)
    vocab = -(-(cfg.n_items + 2) // 16) * 16
    items = jax.random.normal(k[0], (vocab, d), dtype) * d ** -0.5
    pos = jax.random.normal(k[1], (cfg.seq_len, d), dtype) * 0.02
    blocks, blk_lg = [], []
    for i in range(cfg.n_blocks):
        kb = jax.random.split(k[2 + i], 5)
        blocks.append({
            "wqkv": jax.random.normal(kb[0], (d, 3, hN, d // hN), dtype) * d ** -0.5,
            "wo": jax.random.normal(kb[1], (hN, d // hN, d), dtype) * d ** -0.5,
            "ln1": jnp.ones((d,), dtype), "ln2": jnp.ones((d,), dtype),
            "ffn_in": jax.random.normal(kb[2], (d, 4 * d), dtype) * d ** -0.5,
            "ffn_b": jnp.zeros((4 * d,), dtype),
            "ffn_out": jax.random.normal(kb[3], (4 * d, d), dtype) * (4 * d) ** -0.5,
        })
        blk_lg.append({
            "wqkv": ("fsdp", None, "heads", "head_dim"),
            "wo": ("heads", "head_dim", "fsdp"),
            "ln1": ("embed",), "ln2": ("embed",),
            "ffn_in": ("fsdp", "mlp"), "ffn_b": ("mlp",),
            "ffn_out": ("mlp", "fsdp"),
        })
    params = {"items": items, "pos": pos, "blocks": tuple(blocks),
              "ln_f": jnp.ones((d,), dtype)}
    logical = {"items": ("table_rows", "table_dim"), "pos": ("seq", "embed"),
               "blocks": tuple(blk_lg), "ln_f": ("embed",)}
    return params, logical


def _layer_norm(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * w).astype(x.dtype)


def bert4rec_encode(params, cfg: RecsysConfig, item_seq, ctx=None):
    """item_seq: (B, S) -> hidden (B, S, D). Bidirectional attention (S=200
    is tiny; direct scores are fine)."""
    if cfg.tp_lookup and ctx is not None and ctx.mesh is not None:
        from repro.models.embedding import tp_embedding_lookup
        emb = tp_embedding_lookup(params["items"], item_seq, ctx.mesh)
    else:
        emb = jnp.take(params["items"], item_seq, axis=0)
    x = emb + params["pos"]
    for blk in params["blocks"]:
        h = _layer_norm(x, blk["ln1"])
        qkv = jnp.einsum("bsd,dthk->tbshk", h, blk["wqkv"])
        q, k, v = qkv[0], qkv[1], qkv[2]
        sc = jnp.einsum("bshk,bthk->bhst", q, k,
                        preferred_element_type=jnp.float32)
        sc = sc * (q.shape[-1] ** -0.5)
        p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhst,bthk->bshk", p, v)
        x = x + jnp.einsum("bshk,hkd->bsd", o, blk["wo"])
        h2 = _layer_norm(x, blk["ln2"])
        f = jax.nn.gelu(h2 @ blk["ffn_in"] + blk["ffn_b"]) @ blk["ffn_out"]
        x = x + f
    return _layer_norm(x, params["ln_f"])


def bert4rec_forward(params, cfg: RecsysConfig, batch):
    """Masked-item logits over the full item vocab: (B, S, vocab).

    Only viable for small vocabs (smoke tests); production training uses
    the sampled-softmax loss below — a full softmax over 2^20 items at
    batch 65536 x 200 positions is ~5.5e16 bytes of logits.
    """
    hidden = bert4rec_encode(params, cfg, batch["item_seq"])
    return jnp.einsum("bsd,vd->bsv", hidden, params["items"])


def tp_sampled_scores(items, h, cand, mesh):
    """Candidate scores against a row-sharded item table via shard_map.

    items: (V, D) P('model', None); h: (B, M, D); cand: (B, M, C) int32,
    both sharded over the data axes. Each model-rank scores only rows it
    owns and the psum moves LOGITS (B, M, C — tiny) instead of gathered
    embeddings (B, M, C, D). Autodiff scatters d_items into the local row
    shard (§Perf hillclimb 2).
    """
    P = jax.sharding.PartitionSpec
    names = mesh.axis_names
    tp = mesh.shape.get("model", 1)
    v = items.shape[0]
    if tp == 1 or v % tp != 0:
        emb = jnp.take(items, cand, axis=0)
        return jnp.einsum("bmd,bmnd->bmn", h, emb)
    v_loc = v // tp
    dp_axes = tuple(a for a in ("pod", "data") if a in names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    lead = dp_axes if h.shape[0] % max(dp, 1) == 0 and dp > 1 else None
    if isinstance(lead, tuple) and len(lead) == 1:
        lead = lead[0]

    def f(tbl, hl, cl):
        row0 = jax.lax.axis_index("model") * v_loc
        lid = cl - row0
        ok = (lid >= 0) & (lid < v_loc)
        emb = jnp.take(tbl, jnp.clip(lid, 0, v_loc - 1), axis=0)
        emb = emb * ok[..., None].astype(emb.dtype)
        part = jnp.einsum("bmd,bmnd->bmn", hl, emb)
        return jax.lax.psum(part, "model")

    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(P("model", None), P(lead, None, None),
                  P(lead, None, None)),
        out_specs=P(lead, None, None), check_vma=False)(items, h, cand)


def bert4rec_sampled_logits(params, cfg: RecsysConfig, batch, ctx=None):
    """Sampled-softmax cloze logits at masked positions only.

    batch: item_seq (B, S); mask_pos (B, M) int32; mask_labels (B, M);
    neg_ids (B, M, N) pipeline-sampled uniform negatives.
    Returns logits (B, M, 1+N) — index 0 is the true item.
    """
    hidden = bert4rec_encode(params, cfg, batch["item_seq"], ctx=ctx)
    h = jnp.take_along_axis(
        hidden, batch["mask_pos"][..., None], axis=1)           # (B,M,D)
    cand = jnp.concatenate(
        [batch["mask_labels"][..., None], batch["neg_ids"]], -1)  # (B,M,1+N)
    if cfg.tp_lookup and ctx is not None and ctx.mesh is not None:
        return tp_sampled_scores(params["items"], h, cand, ctx.mesh)
    emb = jnp.take(params["items"], cand, axis=0)               # (B,M,1+N,D)
    return jnp.einsum("bmd,bmnd->bmn", h, emb)


# ----------------------------------------------------------- entrypoints ---
FORWARD = {"wide-deep": wide_deep_forward, "xdeepfm": xdeepfm_forward,
           "dien": dien_forward}
INIT = {"wide-deep": init_wide_deep, "xdeepfm": init_xdeepfm,
        "dien": init_dien, "bert4rec": init_bert4rec}


def ctr_loss(params, cfg: RecsysConfig, batch, forward_fn, ctx=None):
    logit = forward_fn(params, cfg, batch, ctx=ctx)
    loss = bce_loss(logit, batch["label"].astype(jnp.float32))
    return loss, {"bce": loss}


def bert4rec_loss(params, cfg: RecsysConfig, batch, ctx=None):
    """Sampled-softmax masked-item loss (true item at index 0)."""
    logits = bert4rec_sampled_logits(params, cfg, batch, ctx=ctx)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    mask = (batch["mask_labels"] >= 0).astype(jnp.float32)
    nll = -logp[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, {"xent": loss}


def bert4rec_full_softmax_loss(params, cfg: RecsysConfig, batch):
    """Full-vocab cloze loss — smoke-test/small-vocab variant."""
    logits = bert4rec_forward(params, cfg, batch)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, {"xent": loss}


def score_candidates(params, cfg: RecsysConfig, user_batch, cand_ids,
                     chunks: int = 1, ctx=None):
    """Retrieval: score ONE user against C candidates -> (C,) scores.

    chunks > 1 scores candidates in `chunks` sequential slabs (lax.map) —
    bounds the live working set for interaction-heavy models (xDeepFM's CIN
    over 1M broadcast candidates would otherwise materialize ~19 GiB of
    per-device intermediates). `ctx` (ShardCtx) re-pins the candidate axis
    after the reshape, which otherwise loses its sharding.
    """
    if chunks > 1:
        c = cand_ids.shape[0]
        assert c % chunks == 0, (c, chunks)
        blocks = cand_ids.reshape(chunks, c // chunks)
        if ctx is not None:
            blocks = ctx.cs(blocks, None, "candidates")

        def one(ids):
            if ctx is not None:
                ids = ctx.cs(ids, "candidates")
            return score_candidates(params, cfg, user_batch, ids, ctx=ctx)
        out = jax.lax.map(one, blocks)
        return out.reshape(c)
    c = cand_ids.shape[0]
    if cfg.name == "bert4rec":
        hidden = bert4rec_encode(params, cfg, user_batch["item_seq"])
        u = hidden[0, -1]                                   # (D,)
        cand = jnp.take(params["items"], cand_ids, axis=0)  # (C, D)
        return cand @ u
    if cfg.name == "dien":
        hist = jnp.take(params["items"], user_batch["hist_ids"], axis=0)
        hist = hist * user_batch["hist_mask"][..., None].astype(hist.dtype)
        states = dien_interest_states(params, hist)         # (1, S, H)
        states_c = jnp.broadcast_to(states, (c,) + states.shape[1:])
        mask_c = jnp.broadcast_to(user_batch["hist_mask"],
                                  (c, states.shape[1]))
        target = jnp.take(params["items"], cand_ids, axis=0)
        interest = dien_evolve(params, states_c, target, mask_c)
        dense = jnp.broadcast_to(user_batch["dense"],
                                 (c, user_batch["dense"].shape[-1]))
        feats = jnp.concatenate(
            [interest, target, dense.astype(interest.dtype)], -1)
        return apply_mlp(params["mlp"], feats)[:, 0]
    # CTR models: candidate replaces sparse feature 0. The USER-side
    # embeddings are computed once (re-gathering them per candidate costs
    # ~22 GiB/device of collectives on wide-deep/retrieval_cand); only
    # the candidate feature's embedding column is gathered per chunk.
    fwd = FORWARD[cfg.name]
    user_emb = multifeature_bag(params["tables"],
                                user_batch["sparse_ids"])   # (1, F, D)
    sp = jnp.broadcast_to(user_batch["sparse_ids"],
                          (c,) + user_batch["sparse_ids"].shape[1:])
    sp = sp.at[:, 0, :].set(cand_ids[:, None] % cfg.vocab_sizes[0])
    dense = jnp.broadcast_to(user_batch["dense"],
                             (c, user_batch["dense"].shape[-1]))
    cand_emb = jnp.take(params["tables"][0],
                        cand_ids % cfg.vocab_sizes[0], axis=0)  # (C, D)
    if cfg.multi_hot > 1:   # bag semantics: candidate id repeated per slot
        cand_emb = cand_emb * cfg.multi_hot
    emb = jnp.concatenate([
        cand_emb[:, None],
        jnp.broadcast_to(user_emb[0, 1:][None],
                         (c, cfg.n_sparse - 1, cfg.embed_dim))], axis=1)
    # NOTE: ctx deliberately NOT forwarded — inside the lax.map chunk loop
    # the act_all_batch constraint forces per-iteration reshards (measured
    # 16x FLOPs regression); candidates are already data-sharded.
    return fwd(params, cfg, {"sparse_ids": sp, "dense": dense}, emb=emb)
