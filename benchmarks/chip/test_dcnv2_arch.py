"""Tests of the DLRM-DCNv2 cell that need no chip.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_dcnv2_arch.py

The cell found by name with everything it reports, the FLOP and byte
counts by hand, the readers of its scopes and of the program's
`ids_looked_up` counter, the packed draw, the reference against the
program's own trainer in float32, and, at a small size through the
whole run, `correct` true on a sound run and false under each fault the
program can have planted (a doubled gradient, half of each batch, the
cross layers left out), while the control and the reference's own
faults each fail a limit.
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import layout  # noqa: E402
import reference  # noqa: E402

CELL = "dlrm-dcnv2-1chip.multihot-cores-all"
DCN = layout.arch("dcnv2")
NEW = ("bag_lookup_device_ms", "bag_lookup_roofline", "cross_net_device_ms",
       "cross_net_roofline")


def tiny(**over) -> dict:
    """The cell at a size the CPU holds: every width and bag size as
    published, each table capped at 2^14 rows (224,802 rows: the packed
    table still takes row-wise Adagrad, as at 2^19) and a batch of 256."""
    cell = layout.cell(CELL)
    cfg = cell["config"]
    cell["config"] = dict(cfg, **{"rows": [min(r, 1 << 14) for r in cfg["rows"]],
                                  "batch": 256, **over})
    return cell


# ---------------------------------------------------------------- layout --
def test_the_cell_and_what_it_reports_are_found_by_name():
    bench = layout.benchmark()
    cell = layout.cell(CELL, bench)
    cfg = cell["config"]
    assert cfg["model"] == "dcnv2" and cell["chips"] == 1
    assert {"bag_lookup", "cross_net", "embedding_lookup", "table_update"} <= set(DCN.SCOPES)
    assert set(cell["limits"]) >= {"loss_gap", "grad_gap", "change_gap", "feed_faults",
                                   "window_nonfinite"}
    # the published widths, bag sizes and interaction; only rows are cut
    assert (cfg["embed_dim"], cfg["bottom_mlp"], cfg["top_mlp"]) == \
        (128, [512, 256, 128], [1024, 1024, 512, 256, 1])
    assert (cfg["dcn_layers"], cfg["dcn_rank"], DCN.width(cfg)) == (3, 512, 3456)
    assert sum(cfg["hot_sizes"]) == 214 and len(cfg["rows"]) == 26
    assert cfg["rows"] == [min(r, 1 << 19) for r in cfg["published"]["rows"]]
    assert sum(cfg["rows"]) == 4_196_496
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == ["rows"] == list(cfg["reduced"])
    # every per-layer metric that lists no cells, and the four that list
    # this one
    traced = [m["name"] for m in layout.metrics_for(CELL, True, bench)]
    assert traced == [m["name"] for m in bench["per_layer"]
                      if CELL in m.get("workloads", [CELL])]
    assert set(NEW) <= set(traced) and "embedding_lookup_roofline" not in traced
    for name in traced:
        assert callable(layout.reader(name))


# ---------------------------------------------------------------- counts --
def test_flops_and_scope_work_by_hand():
    cfg = layout.config("dlrm-dcnv2-1chip")
    bottom = 13 * 512 + 512 * 256 + 256 * 128                  # 170,496 MACs
    top = 3456 * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256
    cross = 3 * 2 * 3456 * 512                                 # V then W, 3 layers
    fwd = 2 * (bottom + top + cross)
    assert DCN.cross_flops(cfg) == 2 * cross == 21_233_664
    assert DCN.train_flops_per_example(cfg) == 3 * fwd - 2 * 13 * 512 == 96_169_472
    work = DCN.scope_work(cfg)
    looked, pooled = 16384 * 214, 16384 * 26
    assert work["bag_lookup"] == {"ops": 128 * (looked - pooled) + 128 * looked,
                                  "bytes": 512 * (looked + pooled + 2 * looked)}
    assert work["bag_lookup"]["bytes"] == 5_603_590_144
    assert work["cross_net"]["ops"] == 3 * 21_233_664 * 16384
    # at the v5e's peaks the lookup is bound by its bytes (6.84 ms) and
    # the cross network by its operations (5.30 ms)
    peaks = layout.peaks("TPU v5 lite")
    assert work["bag_lookup"]["bytes"] / peaks["hbm_bytes_per_s"] > \
        work["bag_lookup"]["ops"] / peaks["bf16_flops_per_s"]
    assert work["cross_net"]["ops"] / peaks["bf16_flops_per_s"] == pytest.approx(5.298e-3, rel=1e-3)
    assert work["cross_net"]["bytes"] / peaks["hbm_bytes_per_s"] < 5.298e-3


@pytest.mark.parametrize("ids,steps,reads", [
    (None, 10, False),                           # the parent: no counter
    (10 * 16384 * 214, 10, True),
    (11 * 16384 * 214, 10, True),                # the step the window closed on
    (10 * 16384 * 26, 10, False),                # one-hot volume: not the bytes counted
    (12 * 16384 * 214, 10, False)])
def test_readers_of_the_new_scopes(ids, steps, reads):
    cfg = layout.config("dlrm-dcnv2-1chip")
    peaks = layout.peaks("TPU v5 lite")
    counters = {} if ids is None else {"ids_looked_up": ids}
    rec = {"spans": {"steps": steps, "counters": counters,
                     "scope_ms": {"bag_lookup": 100.0, "cross_net": 20.0}},
           "peaks": peaks, "config": cfg, "scope_work": DCN.scope_work(cfg)}
    lookup = layout.reader("bag_lookup_roofline")(rec)
    assert (lookup is not None) == reads
    if reads:
        assert lookup == pytest.approx(100 * 5_603_590_144 / 819e9 / 0.1)
    assert layout.reader("bag_lookup_device_ms")(rec) == 100.0
    assert layout.reader("cross_net_device_ms")(rec) == 20.0
    assert layout.reader("cross_net_roofline")(rec) == pytest.approx(
        100 * 3 * 21_233_664 * 16384 / 197e12 / 0.02)
    rec["spans"] = None
    assert all(layout.reader(m)(rec) is None for m in NEW)


# ------------------------------------------------------------- reference --
def test_packed_draw_is_each_tables_own():
    cfg = tiny(rows=[5, 3, 7] + [1] * 23)["config"]
    words = reference.seed_words(2**31 + 12345)
    params = DCN.init_params(words, cfg, jnp.float32)
    table = np.repeat(np.arange(26), cfg["rows"])
    row = np.concatenate([np.arange(n) for n in cfg["rows"]])
    k = jnp.arange(128, dtype=jnp.uint32)
    want = reference.draw(words, DCN.TABLE_LEAF, jnp.asarray(table)[:, None],
                          jnp.asarray(row)[:, None], k[None, :], 128 ** -0.5, jnp.float32)
    assert np.array_equal(np.asarray(params["tables"]), np.asarray(want))
    assert [c["v"].shape for c in params["cross"]] == [(3456, 512)] * 3
    std = float(jnp.std(params["cross"][0]["w"]))
    assert std == pytest.approx((2 / (3456 + 512)) ** 0.5, rel=0.01)


def _batches(cfg, n, seed):
    rng = np.random.RandomState(seed)
    b = cfg["batch"]
    out = []
    for _ in range(n):
        ids = np.concatenate([rng.randint(0, v, (b, h))
                              for v, h in zip(cfg["rows"], cfg["hot_sizes"])], 1)
        out.append({"sparse_ids": ids.astype(np.int32),
                    "dense": rng.randn(b, 13).astype(np.float32),
                    "label": (rng.rand(b) < 0.3).astype(np.float32)})
    return out


def test_reference_follows_the_program_in_float32():
    """The program's own trainer takes the same steps as the reference
    on the CPU, where both compute float32 products in float32: losses,
    first gradient and change agree to rounding."""
    import harness
    cfg = tiny()["config"]
    trainer = harness.make_trainer(cfg, jax.devices()[:1])
    harness.put_weights(trainer, cfg, 7)
    batches = _batches(cfg, 3, 1)
    steps = [(0, batches[0]), (0, batches[0]), (1, batches[1]), (60, batches[2])]
    acc0 = trainer.opt_state
    losses = []
    for n, (i, b) in enumerate(steps):
        losses.append(float(trainer.step(i, trainer.put(b))["loss"]))
        if n == 0:
            grad = jax.device_get(harness._GRAD_FN(acc0, trainer.opt_state, trainer.params))
    change = jax.device_get(harness._change_fn(cfg)(trainer.params, reference.seed_words(7)))
    ref = DCN.replay(7, cfg, steps)
    np.testing.assert_allclose(losses, ref["loss"], rtol=1e-5)
    assert grad.keys() == ref["grad"][0].keys() and "cross/2/v" in grad
    for k, v in ref["grad"][0].items():
        assert grad[k] == pytest.approx(v, rel=1e-4, abs=1e-9), k
    for k, v in ref["change"].items():
        assert change[k] == pytest.approx(v, rel=1e-3, abs=1e-7), k


def test_feed_faults_hold_each_column_to_its_table():
    cfg = tiny()["config"]
    fed = _batches(cfg, 2, 4)
    for b in fed:
        b["dense"] = ((b["dense"] - b["dense"].mean(0)) / b["dense"].std(0)).astype(np.float32)
    assert DCN.feed_faults(fed, cfg) == []
    bad = [{k: v.copy() for k, v in b.items()} for b in fed]
    bad[0]["sparse_ids"][0, 5] = cfg["rows"][2]       # column 5 is table 2's, of 17,295 rows
    assert DCN.feed_faults(bad, cfg) == ["batch 0: ids outside their tables"]
    short = [{**fed[0], "sparse_ids": fed[0]["sparse_ids"][:, :213]}]
    assert DCN.feed_faults(short, cfg) == ["batch 0: sparse_ids is not int32(256, 214)"]


# ------------------------------------------------------- control, faults --
def _run_tiny(seed: int = 2**31 + 77) -> dict:
    import run as runner
    return runner.measure(["--workload", CELL, "--seed", str(seed), "--seconds", "2",
                           "--trace", "0"], devices=jax.devices()[:1], cell=tiny())


def _grad_doubled(loss_fn, optimizer, microbatches=1):
    from repro.train.optim import Optimizer
    from repro.train.train_step import make_train_step

    def update(grads, state, params, step):
        top = list(grads["top"])
        top[0] = {**top[0], "w": top[0]["w"] * 2}
        return optimizer.update({**grads, "top": tuple(top)}, state, params, step)
    return make_train_step(loss_fn, Optimizer(optimizer.name, optimizer.init, update))


def _half_batch_loss(params, cfg, batch, **kw):
    from repro.models import dlrm
    from repro.models.recsys import bce_loss
    logit = dlrm.forward(params, cfg, batch, **kw)
    h = logit.shape[0] // 2
    loss = bce_loss(logit[:h], batch["label"][:h].astype(jnp.float32))
    return loss, {"bce": loss}


@pytest.mark.parametrize("fault", ["", "grad_altered", "half_batch", "cross_skipped"])
def test_sound_run_is_correct_and_each_planted_fault_is_not(monkeypatch, fault):
    from repro.models import dlrm
    from repro.train import feed_loop
    if fault == "grad_altered":
        monkeypatch.setattr(feed_loop, "make_train_step", _grad_doubled)
    elif fault == "half_batch":
        monkeypatch.setattr(dlrm, "loss_fn", _half_batch_loss)
    elif fault == "cross_skipped":
        monkeypatch.setattr(dlrm, "cross_net", lambda layers, x0: x0)
    res = _run_tiny()
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] is (fault == ""), res["checks"]


def test_control_and_the_references_faults_fail_a_limit():
    """The reference in bf16 (the control), and the reference with half
    of each batch, a doubled gradient or the cross layers left out, in
    the program's place: each fails at least one limit."""
    import control
    import harness
    cell = tiny()
    lim = {k: v["limit"] for k, v in cell["limits"].items()}
    row = control.readings(cell, [2**31 + 5], jax.devices()[:1], log=lambda *a, **k: None)[0]
    assert all(row["program"][k] <= lim[k] for k in lim)
    for name in ("control", "half_batch", "grad_altered"):
        assert any(row[name][k] > lim[k] for k in row[name]), name
    # cross_skipped: not among control.py's variants; read as it reads them
    trainer = harness.make_trainer(cell["config"], jax.devices()[:1])
    run = harness.drive(trainer, cell, 2**31 + 5, 0)
    ref = harness.reference_readings(run, cell)
    alt = harness.reference_readings(run, cell, fault="cross_skipped")
    alt = {"loss": alt["loss"][-harness.CHECKED:], "grad": alt["grad"][0],
           "change": alt["change"]}
    gaps = harness.check.training_gaps(alt, ref, harness.CHECKED)
    assert any(gaps[k] > lim[k] for k in gaps), gaps
    harness.stop_tracker()
