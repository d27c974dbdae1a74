"""The program's spans, counters and named scopes (`repro.common.tracing`,
`jax.named_scope`): the span table by hand and under a second thread,
the served loop's spans and counters on a tiny CPU run, the same run's
profiler trace, and the scopes in the compiled train step."""
import glob
import math
import multiprocessing.process
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.common import tracing
from repro.configs.base import DLRMConfig
from repro.data.simulator import MachineSpec
from repro.models import dlrm
from repro.train.feed_loop import DLRMTrainer, criteo_record, train_on_feed
from repro.train.optim import make_optimizer
from repro.train.train_step import make_train_step

TINY = DLRMConfig(name="dlrm-tiny", n_sparse=4, n_dense=13, embed_dim=16,
                  vocab_sizes=(512,) * 4, bottom_mlp=(32, 16), top_mlp=(32, 1),
                  multi_hot=1)
LOOP = ("feed_wait", "step_dispatch", "host_loop", "loss_read")


# ---------------------------------------------------------- the table --
def test_span_table_counts_and_times_nested_spans():
    before = tracing.snapshot()
    with tracing.span("t_outer"):
        for _ in range(3):
            with tracing.span("t_inner"):
                time.sleep(0.01)
    got = tracing.since(before)
    assert got["t_outer"]["n"] == 1 and got["t_inner"]["n"] == 3
    assert got["t_inner"]["s"] >= 0.03
    assert got["t_inner"]["s"] <= got["t_outer"]["s"]
    assert set(got) == {"t_outer", "t_inner"}


def test_span_table_is_exact_under_a_second_thread():
    before = tracing.snapshot()

    def work():
        for _ in range(2000):
            with tracing.span("t_thread"):
                pass
            tracing.count("t_counter", 2)

    threads = [threading.Thread(target=work) for _ in range(2)]
    for t in threads:
        t.start()
    work()
    for t in threads:
        t.join()
    got = tracing.since(before)
    assert got["t_thread"]["n"] == 6000
    assert got["t_counter"] == {"n": 12000, "s": 0.0}


def test_a_span_raising_is_still_counted():
    before = tracing.snapshot()
    with pytest.raises(KeyError):
        with tracing.span("t_raises"):
            raise KeyError("x")
    assert tracing.since(before)["t_raises"]["n"] == 1


def test_tracing_brings_in_no_jax():
    """The pipeline's processes import the tracing module through
    proc_executor; it must not import JAX there (the forkserver preloads
    the data plane without it) and still keeps the table."""
    code = ("import sys\n"
            "import repro.data.featurize\n"
            "from repro.common import tracing\n"
            "with tracing.span('x'):\n"
            "    tracing.count('y', 3)\n"
            "print('jax' in sys.modules, tracing.snapshot()['y']['n'])\n")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.split() == ["False", "3"]


# --------------------------------------------------------- the served loop --
@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """The tiny served loop under a profiler trace: its FeedRun, the
    processes it started, and the trace."""
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    started = []
    orig = multiprocessing.process.BaseProcess.start

    def start(self):
        started.append(self.name)
        return orig(self)

    trainer = DLRMTrainer(TINY, optimizer="rowwise_adagrad", lr=0.02)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    multiprocessing.process.BaseProcess.start = start
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        run = train_on_feed(trainer, criteo_record(TINY, batch=64), steps=5,
                            tune_every=2, machine=MachineSpec(n_cpus=2, mem_mb=4096),
                            log_every=0)
    finally:
        jax.profiler.stop_trace()
        multiprocessing.process.BaseProcess.start = orig
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return run, started, jax.profiler.ProfileData.from_file(path)


def test_loop_spans_once_per_step_and_tick_every_tune_every(traced_run):
    run, _, _ = traced_run
    steps = len(run.losses)
    assert steps == 5
    for name in LOOP:
        assert run.spans[name]["n"] == steps, name
    # ticks after steps 0, 2, 4 are dispatched; each measures, observes,
    # proposes and applies once, with no wait on the device first
    for name in ("tuner_tick", "tick_measure", "tick_observe",
                 "tick_propose", "tick_apply", "pipe_stats"):
        assert run.spans[name]["n"] == 3, name
    assert "tune_sync" not in run.spans
    tick = run.spans["tuner_tick"]["s"]
    parts = sum(run.spans[n]["s"] for n in ("tick_measure", "tick_observe",
                                            "tick_propose", "tick_apply"))
    assert parts <= tick


def test_loop_counts_the_workers_the_pipeline_started(traced_run):
    run, started, _ = traced_run
    assert run.spans["workers_started"]["n"] == len(started) > 0
    assert run.spans["worker_start"]["n"] == len(started)
    assert run.spans.get("workers_retired", {"n": 0})["n"] == \
        run.spans.get("worker_retire", {"n": 0})["n"]
    assert run.spans["rss_pids_read"]["n"] >= run.spans["rss_scan"]["n"] > 0


def test_profiler_trace_holds_the_spans_nested_on_one_host_line(traced_run):
    _, _, data = traced_run
    lines = [list(line.events) for plane in data.planes
             if plane.name.startswith("/host:") for line in plane.lines]
    loop = [evs for evs in lines if any(e.name == "tuner_tick" for e in evs)]
    assert len(loop) == 1
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in loop[0]]

    def named(name):
        return sorted((s, e) for n, s, e in events if n == name)

    def inside(inner, outer):
        return all(any(os <= s and e <= oe for os, oe in named(outer))
                   for s, e in named(inner))

    trains = named("train")
    assert len(trains) == 5
    for name in LOOP + ("tuner_tick",):
        got = named(name)
        if name in ("host_loop", "loss_read"):
            # the last step's loss is read once the loop has ended
            assert got[-1][0] >= trains[-1][1], name
            got = got[:-1]
        assert all(any(ts <= s and e <= te for ts, te in trains)
                   for s, e in got), name
    assert inside("loss_read", "host_loop")
    for name in ("tick_measure", "tick_observe", "tick_propose", "tick_apply"):
        assert len(named(name)) == 3 and inside(name, "tuner_tick"), name
    assert inside("pipe_stats", "tick_measure")

    # a tick runs between its step's dispatch and that step's loss read,
    # which comes after the next step's dispatch; step k's loss is the
    # k-th read
    def within(name, lo, hi):
        return [(s, e) for s, e in named(name) if lo <= s and e <= hi]

    ticks, reads = named("tuner_tick"), named("loss_read")
    assert len(ticks) == 3 and len(reads) == 5
    for k, (ts, te) in zip((0, 2, 4), ticks):
        (lo, hi), = [(s, e) for s, e in trains if s <= ts and te <= e]
        (_, dispatched), = within("step_dispatch", lo, hi)
        assert dispatched <= ts and te <= reads[k][0]
    # the loop's four spans are siblings: none overlaps another
    tiles = sorted(s for name in LOOP[:3] + ("tuner_tick",) for s in named(name))
    assert all(a[1] <= b[0] for a, b in zip(tiles, tiles[1:]))
    # the workers started before the first step carry their counter
    starts = [e for e in loop[0] if e.name == "workers_started"]
    assert starts and all(dict(e.stats)["n"] == 1 for e in starts)


class _Loss:
    """Step i's loss, recording when the loop reads it; a step in
    `running` reports itself still running."""

    def __init__(self, i, arr, events, running):
        self.i, self.arr, self.events, self.running = i, arr, events, running

    def is_ready(self):
        return self.i not in self.running and self.arr.is_ready()

    def __float__(self):
        self.events.append(("loss_read", self.i))
        return float(self.arr)


def _record_steps(trainer, events, running=()):
    """Wrap `trainer.step`: each fed step (the warm-up's steps, which come
    before the first batch's event, pass through) ends on the device
    before it returns, appends ("step", i) to `events` and returns a
    `_Loss`. Returns {i: the parameters step i left}."""
    orig, params_after = trainer.step, {}

    def step(i, batch):
        m = orig(i, batch)
        if not events:
            return m
        jax.block_until_ready(m["loss"])
        params_after[i] = trainer.params
        events.append(("step", i))
        return {**m, "loss": _Loss(i, m["loss"], events, running)}

    trainer.step = step
    return params_after


def _train(trainer, events, steps=5, **hooks):
    return train_on_feed(trainer, criteo_record(TINY, batch=64), steps=steps,
                         tune_every=2, machine=MachineSpec(n_cpus=2, mem_mb=4096),
                         on_batch=lambda i, p, b: events.append(("batch", i)),
                         log_every=0, **hooks)


def test_tick_runs_while_its_step_is_in_flight(monkeypatch):
    """Each tick starts after `trainer.step(i)` returns and ends before
    step i's loss becomes a float, which happens after step i+1 is
    dispatched; `on_batch` keeps its place, `on_step(i)` follows step
    i's loss read and sees step i's own parameters. A tick at whose end
    the newest step had ended counts `ticks_outlasting_step` once: here
    steps 0 and 4 have ended, and step 2 reports itself still running."""
    from repro.api import Session
    events = []
    trainer = DLRMTrainer(TINY, optimizer="rowwise_adagrad", lr=0.02)
    params_after = _record_steps(trainer, events, running={2})
    orig_tick = Session.step

    def tick(self, *args, **kwargs):
        events.append(("tick", "start"))
        out = orig_tick(self, *args, **kwargs)
        events.append(("tick", "end"))
        return out

    def on_step(i, params, opt_state, tuner):
        assert params is params_after[i]
        events.append(("on_step", i))

    monkeypatch.setattr(Session, "step", tick)
    run = _train(trainer, events, on_step=on_step)
    expected = []
    for i in range(5):
        expected += [("batch", i), ("step", i)]
        if i % 2 == 0:
            expected += [("tick", "start"), ("tick", "end")]
        if i > 0:
            expected += [("loss_read", i - 1), ("on_step", i - 1)]
    expected += [("loss_read", 4), ("on_step", 4)]
    assert events == expected
    assert len(run.losses) == 5 and all(map(math.isfinite, run.losses))
    assert run.spans["ticks_outlasting_step"] == {"n": 2, "s": 0.0}


def test_steps_queued_counts_dispatches_behind_a_running_step():
    """`steps_queued` counts each dispatch whose step before reported
    itself still running: steps 0, 3 and 5 do, and only the dispatches
    of steps 1 and 4 come after them."""
    events = []
    trainer = DLRMTrainer(TINY, optimizer="rowwise_adagrad", lr=0.02)
    _record_steps(trainer, events, running={0, 3, 5})
    run = _train(trainer, events, steps=6)
    assert [e for e in events if e[0] == "step"] == [("step", i) for i in range(6)]
    assert run.spans["steps_queued"] == {"n": 2, "s": 0.0}
    # the ticks after steps 2 and 4 found them ended; step 0 ran on
    assert run.spans["ticks_outlasting_step"] == {"n": 2, "s": 0.0}


def test_losses_are_each_steps_own_in_order():
    """`FeedRun.losses` holds, in step order, the float of each loss array
    the step returned, read with no wait added."""
    started, returned = [], []
    trainer = DLRMTrainer(TINY, optimizer="rowwise_adagrad", lr=0.02)
    orig = trainer.step

    def step(i, batch):
        m = orig(i, batch)
        if started:                     # not the warm-up's steps
            returned.append(m["loss"])
        return m

    trainer.step = step
    run = _train(trainer, started, steps=5)
    assert len(returned) == 5
    assert run.losses == [float(a) for a in returned]


def test_a_raising_on_step_leaves_the_next_step_queued():
    """A hook that raises in `on_step(k)` ends the loop with step k+1
    dispatched, its loss unread and its parameters in `trainer.params`."""

    class Stop(Exception):
        pass

    def on_step(i, params, opt_state, tuner):
        events.append(("on_step", i))
        if i == 2:
            raise Stop

    events = []
    trainer = DLRMTrainer(TINY, optimizer="rowwise_adagrad", lr=0.02)
    params_after = _record_steps(trainer, events)
    with pytest.raises(Stop):
        _train(trainer, events, on_step=on_step)
    assert [e for e in events if e[0] == "step"] == [("step", i) for i in range(4)]
    assert [e for e in events if e[0] in ("loss_read", "on_step")] == \
        [(name, i) for i in range(3) for name in ("loss_read", "on_step")]
    assert trainer.params is params_after[3]


# --------------------------------------------------------- the step's scopes --
def test_compiled_step_carries_the_scopes():
    """The gather of the looked-up rows and the scatter of their gradient
    carry `embedding_lookup`, the row-wise update `table_update`, each
    MLP its own scope."""
    params, _ = dlrm.init_params(jax.random.PRNGKey(0), TINY)
    opt = make_optimizer("rowwise_adagrad", lr=0.02, rowwise_min_elems=1024)
    step = make_train_step(lambda p, b: dlrm.loss_fn(p, TINY, b), opt)
    batch = {"sparse_ids": jnp.zeros((64, 4, 1), jnp.int32),
             "dense": jnp.zeros((64, 13)), "label": jnp.zeros((64,))}
    text = jax.jit(step).lower(params, opt.init(params), 0, batch).compile().as_text()
    ops = [(m.group(1), m.group(2)) for m in re.finditer(
        r"= \S+ (\w[\w-]*)\(.*op_name=\"([^\"]*)\"", text)]

    def scoped(kind, scope):
        return [n for k, n in ops if k == kind and re.search(rf"\b{scope}\b", n)]

    assert scoped("gather", "embedding_lookup")
    assert any("transpose" in n for n in scoped("scatter", "embedding_lookup"))
    assert scoped("subtract", "table_update") and scoped("rsqrt", "table_update")
    assert all("optimizer_update" in n for n in
               [n for _, n in ops if "table_update" in n or "dense_update" in n])
    for scope in ("bottom_mlp", "interaction", "top_mlp", "dense_update"):
        assert any(re.search(rf"\b{scope}\b", n) for _, n in ops), scope
