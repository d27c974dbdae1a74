"""Tests of the chip benchmark that need no chip.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

Discovery by name, the FLOP and byte counts by hand, the trace
reduction on a small trace recorded on a v5e, the reference against the
program in float32 and against the numbers it gave before it moved
behind `archs/dlrm.py`, at a small size through the whole run that the
control and every fault the cells can have turn `correct` false, and a
second architecture added to a copy of the benchmark as files alone.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
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
import trace  # noqa: E402

ONE = "dlrm-criteo-1chip.cores-all"
DLRM = layout.arch("dlrm")


def tiny(name: str, **over) -> dict:
    """The cell at a size the CPU holds: every width as published, 4,096
    rows per table and a batch of 1,024."""
    cell = layout.cell(name)
    cell["config"] = dict(cell["config"], **{"rows_per_table": 4096, "batch": 1024, **over})
    return cell


# ---------------------------------------------------------------- layout --
def test_every_cell_and_metric_is_found_by_name():
    bench = layout.benchmark()
    for w in bench["workloads"]:
        cell = layout.cell(w["name"], bench)
        assert cell["config"]["name"] == w["config"]
        assert set(layout.arch(cell["config"]["model"]).SCOPES)
        assert set(cell["limits"]) >= {"loss_gap", "grad_gap", "change_gap",
                                       "feed_faults", "window_nonfinite"}
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert set(c["reduced"]) <= set(layout.config(c["name"])["published"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(layout.reader(m["name"]))
    assert [m["name"] for m in layout.metrics_for(ONE, False, bench)] == \
        ["examples_per_s", "peak_hbm_gb", "setup_s"]
    assert len(layout.metrics_for(ONE, True, bench)) == len(bench["per_layer"])
    assert layout.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


@pytest.mark.parametrize("bad", ["../BENCHMARK", "a/b", "", "no such cell",
                                 "no_such_model"])
def test_unknown_or_unsafe_names_are_refused(bad):
    with pytest.raises(layout.LayoutError):
        layout.cell(bad)
    with pytest.raises(layout.LayoutError, match="archs/no_such_model.py"
                       if bad == "no_such_model" else "not a benchmark name"):
        layout.arch(bad)
    with pytest.raises(layout.LayoutError):
        layout.peaks("TPU v9 imaginary")


# ----------------------------------------------------------------- flops --
def test_flops_per_example_by_hand():
    cfg = layout.config("dlrm-criteo-1chip")
    bottom = 13 * 512 + 512 * 256 + 256 * 128             # 170,496 MACs
    pairs = 27 * 26 // 2                                  # 351
    top = (351 + 128) * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256
    fwd = 2 * (bottom + top) + 2 * 128 * pairs            # 4,820,224
    assert DLRM.forward_flops(cfg) == fwd == 4_820_224
    # backward: two products per layer and per pair dot, but no input
    # gradient for the first bottom layer
    assert DLRM.train_flops_per_example(cfg) == 3 * fwd - 2 * 13 * 512 \
        == 14_447_360


def test_scope_work_by_hand():
    cfg = layout.config("dlrm-criteo-1chip")
    looked = 65536 * 26 * 1                               # rows looked up a step
    row = 128 * 4                                         # float32 bytes a row
    work = DLRM.scope_work(cfg)
    # lookup: gather, pooled output, the gradient's read-modify-write
    assert work["embedding_lookup"] == {"ops": 128 * looked,
                                        "bytes": row * (looked + looked + 2 * looked)}
    assert work["embedding_lookup"]["bytes"] == 3_489_660_928
    # update: 26 x 2^18 x 128 > 2^24 elements, so row-wise Adagrad: each
    # row and its one float32 accumulator, read and written
    assert work["table_update"] == {"ops": looked * (5 * 128 + 4),
                                    "bytes": 2 * looked * (row + 4)}
    assert work["table_update"]["bytes"] == 1_758_461_952
    small = dict(cfg, rows_per_table=4096, multi_hot=3, batch=1024)
    work = DLRM.scope_work(small)                         # element-wise Adagrad
    assert work["embedding_lookup"]["ops"] == 128 * (1024 * 26 * 2 + 1024 * 26 * 3)
    assert work["table_update"] == {"ops": 1024 * 26 * 3 * 7 * 128,
                                    "bytes": 2 * 1024 * 26 * 3 * (row + row)}
    # at the v5e's peaks the lookup is bound by its bytes: 4.26 ms a step
    peaks = layout.peaks("TPU v5 lite")
    rec = {"spans": {"scope_ms": {"embedding_lookup": 50.0}}, "peaks": peaks,
           "scope_work": DLRM.scope_work(cfg)}
    assert layout.reader("embedding_lookup_roofline")(rec) == pytest.approx(
        100 * 3_489_660_928 / 819e9 / 50e-3)
    assert layout.reader("table_update_roofline")(rec) is None


# ----------------------------------------------------------------- trace --
def test_trace_reduction_by_hand():
    ev = {"devices": {
        "/device:TPU:0": {"ops": [["a", 0, 10], ["b", 5, 10], ["c", 30, 10]],
                          "modules": [["jit_train_step(1)", 0, 15],
                                      ["jit_train_step(1)", 30, 10]]},
        "/device:TPU:1": {"ops": [["a", 0, 20]], "modules": []}},
        "host": [["bench_window", 0, 50], ["feed_wait", 15, 15],
                 ["step_dispatch", 30, 20]]}
    r = trace.reduce(ev)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(22.5e-9)          # (25 + 20) / 2
    assert r["idle_share"] == pytest.approx(0.55)
    assert r["step_device_s"] == pytest.approx(12.5e-9)
    assert layout.reader("train_step_device_ms")({"trace": r}) == pytest.approx(12.5e-6)
    assert [n for n, _ in r["device_ops"]] == ["a", "b", "c"]
    assert r["device_ops"][0][1] == pytest.approx(15e-9)
    assert r["idle_gaps"] == [["feed_wait", pytest.approx(15e-9)],
                              ["step_dispatch", pytest.approx(10e-9)]]


def test_trace_reduction_of_a_recorded_trace():
    with open(os.path.join(HERE, "testdata", "v5e_trace_events.json"),
              encoding="utf-8") as fh:
        ev = json.load(fh)
    r = trace.reduce(ev)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 <= r["idle_share"] < 1
    assert r["step_calls"] >= 1 and r["step_device_s"] > 0
    assert {n for n, _ in r["idle_gaps"]} <= set(trace.SPANS) | {"unattributed"}
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


# ------------------------------------------------------------- reference --
def test_drawn_rows_equal_the_whole_table_draw():
    cfg = tiny(ONE)["config"]
    words = reference.seed_words(2**31 + 12345)
    params = DLRM.init_params(words, cfg, jnp.bfloat16)
    keys = np.array([0, 5, 4095, 4096 * 7 + 11, 4096 * 26 - 1])
    rows = DLRM.table_rows(words, jnp.asarray(keys // 4096), jnp.asarray(keys % 4096),
                           cfg, jnp.bfloat16)
    whole = params["tables"].reshape(-1, cfg["embed_dim"])[keys]
    assert np.array_equal(np.asarray(rows, np.float32), np.asarray(whole, np.float32))
    std = float(jnp.std(params["tables"].astype(jnp.float32)))
    assert std == pytest.approx(cfg["embed_dim"] ** -0.5, rel=0.01)


@pytest.mark.parametrize("rows", [4096, 8192])   # element-wise, row-wise tables
def test_reference_follows_the_program_in_float32(rows):
    """The program's own trainer at float32 takes the same steps as the
    reference: losses, first gradient and change agree to rounding."""
    import harness
    cell = tiny(ONE, rows_per_table=rows, param_dtype="float32")
    cfg = cell["config"]
    trainer = harness.make_trainer(cfg, jax.devices()[:1])
    harness.put_weights(trainer, cfg, 7)
    rng = np.random.RandomState(1)
    batches = [{"sparse_ids": rng.randint(0, rows, (1024, 26, 1)).astype(np.int32),
                "dense": rng.randn(1024, 13).astype(np.float32),
                "label": (rng.rand(1024) < 0.3).astype(np.float32)} for _ in range(3)]
    steps = [(0, batches[0]), (0, batches[0]), (1, batches[1]), (60, batches[2])]
    acc0 = trainer.opt_state
    losses = []
    for n, (i, b) in enumerate(steps):
        losses.append(float(trainer.step(i, trainer.put(b))["loss"]))
        if n == 0:
            grad = jax.device_get(harness._GRAD_FN(acc0, trainer.opt_state, trainer.params))
    change = jax.device_get(harness._change_fn(cfg)(trainer.params, reference.seed_words(7)))
    ref = DLRM.replay(7, cfg, steps)
    np.testing.assert_allclose(losses, ref["loss"], rtol=1e-5)
    for k, v in ref["grad"][0].items():
        assert grad[k] == pytest.approx(v, rel=1e-4, abs=1e-9), k
    for k, v in ref["change"].items():
        assert change[k] == pytest.approx(v, rel=1e-3, abs=1e-7), k


GOLDEN = os.path.join(HERE, "testdata", "dlrm_parent_tiny.json")


def _golden_steps(rows: int):
    """[(optimizer step, host batch)] as the harness replays them: the
    warm batch at the warm-up's steps, then three fed batches."""
    import harness
    rng = np.random.RandomState(3)

    def batch():
        return {"sparse_ids": rng.randint(0, rows, (1024, 26, 1)).astype(np.int32),
                "dense": rng.randn(1024, 13).astype(np.float32),
                "label": (rng.rand(1024) < 0.3).astype(np.float32)}
    warm = batch()
    return ([(s, warm) for s in harness.WARM_STEPS]
            + list(enumerate(batch() for _ in range(3))))


def _golden_fed(rows: int):
    """Three batches as featurization leaves them, and the same with one
    fault planted in each (an id past the table, a label of 2, a repeated
    row, a short dense feature)."""
    rng = np.random.RandomState(5)
    fed = []
    for _ in range(3):
        dense = rng.randn(1024, 13).astype(np.float32)
        dense = ((dense - dense.mean(0)) / dense.std(0)).astype(np.float32)
        fed.append({"sparse_ids": rng.randint(0, rows, (1024, 26, 1)).astype(np.int32),
                    "dense": dense,
                    "label": (rng.rand(1024) < 0.3).astype(np.float32)})
    bad = [{k: v.copy() for k, v in b.items()} for b in fed]
    bad[0]["sparse_ids"][0, 3, 0] = rows
    bad[1]["label"][7] = 2.0
    bad[2]["sparse_ids"][9] = bad[2]["sparse_ids"][8]
    bad[2]["dense"][9] = bad[2]["dense"][8]
    short = {**fed[0], "dense": fed[0]["dense"][:, :12]}
    return [fed] + [fed[:k] + [b] + fed[k + 1:] for k, b in zip((0, 1, 2, 0), bad + [short])]


@pytest.mark.parametrize("rows", [4096, 8192])   # element-wise, row-wise tables
def test_dlrm_arch_gives_the_numbers_it_gave_before_the_move(rows):
    """`archs/dlrm.py` over the generic replay reads what the benchmark's
    DLRM reference read before it moved there (recorded in testdata from
    the parent tree, on this CPU backend): losses, every step's gradient
    norms and the change, for the reference, the control and each fault;
    the FLOP count; the fed batches' faults."""
    with open(GOLDEN, encoding="utf-8") as fh:
        gold = json.load(fh)
    cfg = dict(layout.config("dlrm-criteo-1chip"), rows_per_table=rows, batch=1024)
    steps = _golden_steps(rows)
    for name, quant, fault, shards in [
            ("float32", "float32", "", 1), ("control", "bfloat16", "", 1),
            ("half_batch", "float32", "half_batch", 1),
            ("grad_altered", "float32", "grad_altered", 1),
            ("exchange_left_out", "float32", "exchange_left_out", 4)]:
        got = DLRM.replay(gold["seed"], cfg, steps, quant=quant, fault=fault, shards=shards)
        want = gold["replay"][f"{rows}.{name}"]
        # 1e-7 relative: the arithmetic did not change, only where it is
        # written, so only a compiler's re-association of a float32 sum
        # could move a number, by under an ulp (1.19e-7 relative at most)
        np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-7, err_msg=name)
        for g, w in zip(got["grad"], want["grad"]):
            assert g.keys() == w.keys()
            for k in w:
                assert g[k] == pytest.approx(w[k], rel=1e-7), (name, k)
        assert got["change"].keys() == want["change"].keys()
        for k, w in want["change"].items():
            assert got["change"][k] == pytest.approx(w, rel=1e-7), (name, k)
    assert [DLRM.feed_faults(b, cfg) for b in _golden_fed(rows)] == \
        gold["feed_faults"][str(rows)]
    assert DLRM.train_flops_per_example(layout.config("dlrm-criteo-1chip")) == \
        gold["flops_per_example"]


# ------------------------------------------------------- control, faults --
def _run_tiny(name: str, seed: int = 2**31 + 77) -> dict:
    import run as runner
    cell = tiny(name)
    return runner.measure(["--workload", name, "--seed", str(seed), "--seconds", "2",
                           "--trace", "0"], devices=jax.devices()[:cell["chips"]],
                          cell=cell)


def test_sound_run_is_correct():
    res = _run_tiny(ONE)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_fails():
    """The reference in the precision below the configuration's, in the
    program's place, fails a limit."""
    import control
    cell = tiny(ONE)
    row = control.readings(cell, [2**31 + 5], jax.devices()[:1], log=lambda *a, **k: None)[0]
    lim = {k: v["limit"] for k, v in cell["limits"].items()}
    assert all(row["program"][k] <= lim[k] for k in lim)
    assert any(row["control"][k] > lim[k] for k in row["control"])


def _step_unchanged(loss_fn, optimizer, microbatches=1):
    from repro.train.train_step import make_train_step
    step = make_train_step(loss_fn, optimizer)

    def frozen(params, opt_state, i, batch):
        _, _, metrics = step(params, opt_state, i, batch)
        return params, opt_state, metrics
    return frozen


def _grad_doubled(loss_fn, optimizer, microbatches=1):
    from repro.train.optim import Optimizer
    from repro.train.train_step import make_train_step

    def update(grads, state, params, step):
        top = list(grads["top"])
        top[0] = {**top[0], "w": top[0]["w"] * 2}
        return optimizer.update({**grads, "top": tuple(top)}, state, params, step)
    return make_train_step(loss_fn, Optimizer(optimizer.name, optimizer.init, update))


def _window_loss_nan(loss_fn, optimizer, microbatches=1):
    """The step as built, with every loss after the checked steps not a
    number: read only through the losses the harness keeps."""
    from repro.train.train_step import make_train_step
    step = make_train_step(loss_fn, optimizer)

    def nan_later(params, opt_state, i, batch):
        params, opt_state, metrics = step(params, opt_state, i, batch)
        loss = jnp.where(i >= 3, jnp.nan, metrics["loss"])
        return params, opt_state, {**metrics, "loss": loss}
    return nan_later


def _half_batch_loss(params, cfg, batch, **kw):
    from repro.models import dlrm
    from repro.models.recsys import bce_loss
    logit = dlrm.forward(params, cfg, batch, **kw)
    h = logit.shape[0] // 2
    loss = bce_loss(logit[:h], batch["label"][:h].astype(jnp.float32))
    return loss, {"bce": loss}


@pytest.mark.parametrize("fault", ["step_unchanged", "half_batch", "grad_altered",
                                   "window_loss_nan"])
def test_fault_turns_correct_false(monkeypatch, fault):
    from repro.models import dlrm
    from repro.train import feed_loop
    if fault == "step_unchanged":
        monkeypatch.setattr(feed_loop, "make_train_step", _step_unchanged)
    elif fault == "grad_altered":
        monkeypatch.setattr(feed_loop, "make_train_step", _grad_doubled)
    elif fault == "window_loss_nan":
        monkeypatch.setattr(feed_loop, "make_train_step", _window_loss_nan)
    else:
        monkeypatch.setattr(dlrm, "loss_fn", _half_batch_loss)
    res = _run_tiny(ONE)
    assert res["correct"] is False
    if fault == "window_loss_nan":
        assert res["checks"]["window_nonfinite"]["value"] == res["attempted"] > 0


# ------------------------------------------------- another architecture --
TOY = os.path.join(HERE, "testdata", "toy")
# Runs in a process of its own, from the copy: the copy's modules, its
# BENCHMARK.json and the toy's files, with nothing of this process's.
TOY_RUN = r"""
import json, os, sys
root, seed = sys.argv[1], sys.argv[2]
sys.path.insert(0, os.path.join(root, "benchmarks", "chip"))
import jax
import layout, run, spans
name = "toy-2tables.multi-hot"
argv = ["--workload", name, "--seed", seed, "--seconds", "2", "--trace", "0"]
out = {"sound": run.measure(argv, devices=jax.devices()[:1])}

# planted in the program: the step returns its state unchanged
arch = layout.arch("toy")
build = arch.make_trainer
def unchanged(cfg, mesh):
    trainer = build(cfg, mesh)
    step = trainer._step
    trainer._step = lambda p, o, i, b: (p, o, step(p, o, i, b)[2])
    return trainer
arch.make_trainer = unchanged
out["step_unchanged"] = run.measure(argv, devices=jax.devices()[:1])
arch.make_trainer = build

# three toy steps under the profiler: its span, counter and scopes reach
# the reduction by their names alone; the CPU has no device plane, so
# each step's module is given one operation of the cross layer
from repro.train.feed_loop import warm_batch
cell = layout.cell(name)
cfg = cell["config"]
trainer = arch.make_trainer(cfg, None)
batch = trainer.put(warm_batch(arch.record_spec(cfg, cell["traffic"], 1)))
trainer.step(0, batch)
jax.block_until_ready(trainer.params)
d = os.path.join(root, "trace")
jax.profiler.start_trace(d)
with jax.profiler.TraceAnnotation(spans.WINDOW):
    for i in range(3):
        with jax.profiler.StepTraceAnnotation(spans.STEP, step_num=i):
            trainer.step(i, batch)
            jax.block_until_ready(trainer.params)
jax.profiler.stop_trace()
ev = spans.extract(d, arch.SCOPES, arch.SPANS)
mod, scopes = next((m, s) for m, s in sorted(ev["scopes"].items())
                   if "toy_cross" in s.values())
cross = next(k for k, s in scopes.items() if s == "toy_cross")
steps = [e for evs in ev["host"].values() for e in evs if e[0] == spans.STEP]
ev["devices"] = {"/device:TPU:0": {"modules": [[mod, s, d] for _, s, d, _ in steps],
                                   "ops": [[cross, s, d / 2] for _, s, d, _ in steps]}}
red = spans.reduce(ev, spans=arch.SPANS)
rec = {"spans": red, "peaks": None, "config": cfg, "scope_work": arch.scope_work(cfg)}
out["scopes"] = sorted(set(scopes.values()))
out["toy_step"] = red["spans"]["toy_step"]["n"]
out["readers"] = {m: layout.reader(m)(rec) for m in ("toy_cross_device_ms", "toy_rows_per_step")}
print(json.dumps(out))
"""


def test_a_new_architecture_is_added_as_files(tmp_path):
    """A toy of another shape (two tables of unequal rows, unequal hot
    sizes, a cross layer under its own scope, a host span and a counter)
    runs through `run.measure` in a copy of the benchmark to which only
    its files were added: `correct` holds, and a step that returns its
    state unchanged turns it false; its readers read its scope and its
    counter from a profiler trace."""
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(HERE, chip, ignore=shutil.ignore_patterns("out", "__pycache__"))
    bench = layout.benchmark()
    for dirpath, _, files in os.walk(TOY):
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), TOY)
            if rel == "BENCHMARK.add.json":
                with open(os.path.join(TOY, rel), encoding="utf-8") as fh:
                    for key, entries in json.load(fh).items():
                        bench[key] = bench[key] + entries
                continue
            assert not (chip / rel).exists(), f"{rel} would edit a benchmark file"
            (chip / rel).parent.mkdir(parents=True, exist_ok=True)
            shutil.copyfile(os.path.join(dirpath, f), chip / rel)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                           os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", TOY_RUN, str(root), str(2**31 + 901)],
                          cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert out["sound"]["attempted"] > 0 and out["sound"]["failed"] == 0
    assert out["step_unchanged"]["correct"] is False
    # no leaf moved: the change reads 1 but for the round-off of the
    # program's own change (params - the drawn weights, in one fusion)
    assert out["step_unchanged"]["checks"]["change_gap"]["value"] > 0.99
    assert {"toy_lookup", "toy_cross", "toy_head", "table_update"} <= set(out["scopes"])
    assert out["toy_step"] == 3
    assert out["readers"]["toy_cross_device_ms"] > 0
    assert out["readers"]["toy_rows_per_step"] == 256 * (1 + 3)
