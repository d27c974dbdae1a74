"""data/stream.py + the streaming plane (ISSUE 7): arrival-process
determinism and exact integrals, the simulator's backlog/staleness/shed
accounting against hand-computed recursions, backlog-driven OOM, the
process plane's rate-limited source pacing, the controller's
staleness-AGING reward and its re-adaptation triggers, and (slow) the
fig_stream sim acceptance run."""
import multiprocessing as mp
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.api.telemetry import Telemetry
from repro.core.controller import InTune
from repro.data.pipeline import StageGraph, StageSpec
from repro.data.proc_executor import StreamSourceWork
from repro.data.simulator import Allocation, MachineSpec, PipelineSim
from repro.data.stream import ArrivalProcess, flash_crowd_arrivals


# ------------------------------------------------------ arrival process --

def test_arrivals_deterministic_under_seed():
    kw = dict(users=4096.0, events_per_user_s=1.0, burst_every_s=30.0,
              burst_len_s=5.0, burst_gain=3.0, horizon_s=600.0)
    a, b = ArrivalProcess(seed=7, **kw), ArrivalProcess(seed=7, **kw)
    assert a._bursts == b._bursts
    ts = np.linspace(0.0, 600.0, 601)
    assert [a.events_per_sec(t) for t in ts] \
        == [b.events_per_sec(t) for t in ts]
    c = ArrivalProcess(seed=8, **kw)
    assert a._bursts != c._bursts


def test_constant_rate_integral_is_exact():
    arr = ArrivalProcess(users=4096.0, events_per_user_s=1.0,
                         events_per_batch=4096.0)
    # 1 batch/s, no shape: the integral is just the elapsed time
    assert arr.batches_per_sec(123.0) == pytest.approx(1.0)
    assert arr.batches_between(10.0, 17.5) == pytest.approx(7.5)
    assert arr.batches_before(42.0) == pytest.approx(42.0)
    assert arr.batches_between(5.0, 5.0) == 0.0
    assert arr.batches_between(9.0, 3.0) == 0.0


def test_flash_crowd_integral_splits_at_boundaries():
    arr = flash_crowd_arrivals(2.0, spike_at_s=10.0, spike_len_s=4.0,
                               spike_gain=10.0)
    # window straddles the spike start: 2 s at 2 b/s + 3 s at 20 b/s
    assert arr.batches_between(8.0, 13.0) == pytest.approx(2 * 2 + 3 * 20)
    # wholly inside / wholly outside
    assert arr.batches_between(10.0, 14.0) == pytest.approx(4 * 20)
    assert arr.batches_between(14.0, 20.0) == pytest.approx(6 * 2)


def test_diurnal_integral_matches_quadrature():
    arr = ArrivalProcess(users=4096.0, events_per_user_s=2.0,
                         diurnal_amp=0.4, diurnal_period_s=97.0,
                         diurnal_phase_s=13.0,
                         flash_crowds=((20.0, 11.0, 5.0),))
    ts = np.linspace(3.0, 71.0, 200001)
    rates = np.array([arr.events_per_sec(t) for t in ts])
    numeric = float(np.trapezoid(rates, ts))
    assert arr.events_between(3.0, 71.0) == pytest.approx(numeric, rel=1e-6)


def test_diurnal_amp_bounds():
    with pytest.raises(ValueError):
        ArrivalProcess(diurnal_amp=1.0)


# ----------------------------------------------------- sim stream plane --

def _stream_spec(arrival, cost=0.5, **graph_kw):
    stages = (
        StageSpec("src", "stream", cost=cost, arrival=arrival),
        StageSpec("sink", "batch", cost=cost, inputs=("src",)),
    )
    return StageGraph("t_stream", stages, batch_mb=1.0,
                      target_rate=arrival.batches_per_sec(0.0), **graph_kw)


def test_sim_backlog_and_staleness_recursion():
    """The reported freshness metrics must satisfy the definitional
    recursion bl_k = max(0, bl_{k-1} + arrivals_k - tput_k * tick_s) and
    stale = backlog / drain rate, with arrivals the exact integral."""
    arr = ArrivalProcess(users=5 * 4096.0, events_per_user_s=1.0)  # 5 b/s
    spec = _stream_spec(arr, cost=0.5)  # 1 worker/stage => 2 b/s capacity
    sim = PipelineSim(spec, MachineSpec(n_cpus=8, mem_mb=8192.0),
                      obs_noise=0.0)
    alloc = Allocation(np.array([1, 1], dtype=int), prefetch_mb=2.0)
    bl = 0.0
    for k in range(10):
        out = sim.apply(alloc)
        arrivals = arr.batches_between(float(k), float(k + 1))
        assert out["arrival_rate"] == pytest.approx(arrivals)
        bl = max(0.0, bl + arrivals - out["throughput"] * 1.0)
        assert out["backlog_items"] == pytest.approx(bl)
        assert out["batch_staleness_s"] == pytest.approx(
            bl / out["throughput"])
        assert out["p99_queue_delay_s"] >= 0.0
    # undersized by 3 b/s: ten ticks of unbounded growth
    assert bl == pytest.approx(30.0, abs=1.0)


def test_sim_stream_caps_service_at_arrivals():
    """An overprovisioned allocation cannot process events that have not
    happened: throughput tracks the arrival rate and backlog stays 0."""
    arr = ArrivalProcess(users=4096.0, events_per_user_s=1.0)  # 1 b/s
    spec = _stream_spec(arr, cost=0.01)  # 100 b/s per worker
    sim = PipelineSim(spec, MachineSpec(n_cpus=8, mem_mb=8192.0),
                      obs_noise=0.0)
    alloc = Allocation(np.array([2, 2], dtype=int), prefetch_mb=2.0)
    for _ in range(5):
        out = sim.apply(alloc)
        assert out["throughput"] == pytest.approx(1.0, rel=0.01)
        assert out["backlog_items"] == pytest.approx(0.0, abs=1e-6)
        assert out["batch_staleness_s"] == pytest.approx(0.0, abs=1e-6)


def test_sim_ooms_on_backlog_growth():
    """Backlogged batches hold buffer memory: an undersized allocation
    must OOM from backlog growth alone, and the backlog keeps accruing
    through the restart dead window (the world does not pause)."""
    arr = ArrivalProcess(users=5 * 4096.0, events_per_user_s=1.0,
                         buffer_mb_per_batch=50.0)
    spec = _stream_spec(arr, cost=0.5)
    sim = PipelineSim(spec, MachineSpec(n_cpus=8, mem_mb=1000.0),
                      obs_noise=0.0)
    alloc = Allocation(np.array([1, 1], dtype=int), prefetch_mb=2.0)
    outs = [sim.apply(alloc) for _ in range(20)]
    assert sim.oom_count >= 1
    first = next(i for i, o in enumerate(outs) if o["oom"])
    # dead window: no draining, backlog strictly grows
    assert outs[first + 1]["restarting"]
    assert outs[first + 1]["backlog_items"] > outs[first]["backlog_items"]


def test_sim_retention_cap_sheds():
    arr = ArrivalProcess(users=5 * 4096.0, events_per_user_s=1.0,
                         buffer_cap_batches=4.0)
    spec = _stream_spec(arr, cost=0.5)
    sim = PipelineSim(spec, MachineSpec(n_cpus=8, mem_mb=8192.0),
                      obs_noise=0.0)
    alloc = Allocation(np.array([1, 1], dtype=int), prefetch_mb=2.0)
    out = None
    for _ in range(10):
        out = sim.apply(alloc)
        assert out["backlog_items"] <= 4.0 + 1e-9
    assert out["shed_batches"] > 0.0


def test_non_stream_spec_reports_no_freshness():
    from repro.data.pipeline import criteo_pipeline
    sim = PipelineSim(criteo_pipeline(), MachineSpec(), obs_noise=0.0)
    out = sim.apply(Allocation(np.array([1, 1, 1, 1, 1], dtype=int),
                               prefetch_mb=2.0))
    assert "backlog_items" not in out and "batch_staleness_s" not in out


# ---------------------------------------------------- proc source pacing --

def test_stream_source_work_paces_emissions():
    """In-process (no forks): a StreamSourceWork must emit at the arrival
    curve's pace, not the CPU's — the token bucket is the same integral
    the simulator scores."""
    arr = ArrivalProcess(users=40 * 4096.0, events_per_user_s=1.0)  # 40 b/s
    work = StreamSourceWork(cost=1e-4, arrival=arr)
    emitted = mp.Value("L", 0)
    t0 = mp.Value("d", time.monotonic())
    work.attach_stream(emitted, t0)
    deadline = time.monotonic() + 0.5
    while time.monotonic() < deadline:
        work()
    elapsed = time.monotonic() - t0.value
    available = arr.batches_before(elapsed)
    # never ahead of the world; CPU is ~100x faster than the curve, so
    # it should also not fall meaningfully behind it
    assert emitted.value <= available + 1e-9
    assert emitted.value >= 0.5 * available


def test_stream_source_work_unthrottled_without_attach():
    arr = ArrivalProcess(users=4096.0, events_per_user_s=1.0)  # 1 b/s
    work = StreamSourceWork(cost=1e-4, arrival=arr)
    # no attach_stream: degrades to a plain source, no pacing, no skips
    for _ in range(50):
        assert work() is not None


# ------------------------------------------------- controller freshness --

def _tuner(**kw):
    arr = flash_crowd_arrivals(2.0, spike_at_s=1e9, spike_len_s=1.0)
    spec = _stream_spec(arr, cost=0.1)
    machine = MachineSpec(n_cpus=8, mem_mb=4096.0)
    tuner = InTune(spec, machine, seed=0, head="factored",
                   init_alloc=Allocation(np.array([1, 1], dtype=int),
                                         prefetch_mb=2.0), **kw)
    return spec, machine, tuner


def _tel(tput=5.0, stale=None):
    return Telemetry(throughput=tput, mem_mb=64.0, used_cpus=2,
                     batch_staleness_s=stale,
                     backlog_items=None if stale is None else stale * tput)


def test_reward_charges_staleness_growth_not_level():
    """Absolute staleness is non-stationary across a spike (minute 5
    scores worse than minute 1 under the SAME allocation); the reward
    must charge the per-window GROWTH instead."""
    spec, machine, tuner = _tuner(stale_scale=1.0)
    tuner.propose(spec, machine, None)
    tuner.observe(_tel(stale=3.0))          # aging 3 from a fresh start
    r_growing = tuner.history[-1]["reward"]
    tuner.propose(spec, machine, None)
    tuner.observe(_tel(stale=3.0))          # same level, aging 0
    r_holding = tuner.history[-1]["reward"]
    tuner.propose(spec, machine, None)
    tuner.observe(_tel(stale=1.0))          # draining: aging clamped to 0
    r_draining = tuner.history[-1]["reward"]
    assert r_growing == pytest.approx(r_holding / 4.0)   # 1/(1+3/1)
    assert r_holding == pytest.approx(r_draining)
    # and a fresh pipe scores the same as a draining one at equal tput
    tuner.propose(spec, machine, None)
    tuner.observe(_tel(stale=0.0))
    assert tuner.history[-1]["reward"] == pytest.approx(r_holding)


def test_readapt_reopens_on_unimproving_staleness():
    spec, machine, tuner = _tuner(finetune_ticks=2, readapt_stale_s=1.0,
                                  readapt_drift=0.0)
    for stale in (0.0, 0.0):                # tuning window, then serving
        tuner.propose(spec, machine, None)
        tuner.observe(_tel(stale=stale))
    assert tuner.ticks_since_reset == 2     # serving; baseline stale 0.0
    tuner.propose(spec, machine, None)
    tuner.observe(_tel(stale=5.0))          # over the line, not improving
    assert tuner.ticks_since_reset == 0     # exploration reopened
    assert tuner.best == (-1.0, None)


def test_readapt_leaves_a_draining_incumbent_alone():
    """The progress guard: staleness over the line but IMPROVING since
    serving began means the incumbent is draining a spike's backlog at
    full rate — reopening would trade it for an exploration storm."""
    spec, machine, tuner = _tuner(finetune_ticks=2, readapt_stale_s=1.0,
                                  readapt_drift=0.0)
    tuner.propose(spec, machine, None)
    tuner.observe(_tel(stale=0.0))
    tuner.propose(spec, machine, None)
    tuner.observe(_tel(stale=9.0))          # serving starts: baseline 9
    for stale in (8.0, 7.0, 6.0):           # still > 1.0 but draining
        tsr = tuner.ticks_since_reset
        tuner.propose(spec, machine, None)
        tuner.observe(_tel(stale=stale))
        assert tuner.ticks_since_reset == tsr + 1   # no reopen
    tuner.propose(spec, machine, None)
    tuner.observe(_tel(stale=9.5))          # progress lost: reopen
    assert tuner.ticks_since_reset == 0


def test_readapt_drift_is_downward_only():
    """Throughput rising while fresh is a served demand surge (nothing
    to fix); only the downward drift of a trough reopens exploration."""
    spec, machine, tuner = _tuner(finetune_ticks=2, readapt_stale_s=0.0,
                                  readapt_drift=0.5)
    for _ in range(4):                      # serving, EWMA ref ~= 10
        tuner.propose(spec, machine, None)
        tuner.observe(_tel(tput=10.0, stale=0.0))
    assert tuner.ticks_since_reset == 4
    tuner.propose(spec, machine, None)
    tuner.observe(_tel(tput=30.0, stale=0.0))   # upward surge: hold
    assert tuner.ticks_since_reset == 5
    # EWMA moved toward 30; re-anchor it near 10 before the trough
    for _ in range(6):
        tuner.propose(spec, machine, None)
        tuner.observe(_tel(tput=10.0, stale=0.0))
    tuner.propose(spec, machine, None)
    tuner.observe(_tel(tput=2.0, stale=0.0))    # trough: reopen
    assert tuner.ticks_since_reset == 0


# ------------------------------------------------------- slow acceptance --

@pytest.mark.slow
def test_fig_stream_sim_acceptance():
    """ISSUE 7 acceptance (sim plane): under a 10x flash crowd the tuned
    arm re-adapts within half of the best frozen arm's sustained
    starvation window with zero OOMs, while both frozen arms fail."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks import fig_stream
    res = fig_stream.run_sim(seed=0)
    assert all(res["pass"].values()), res["pass"]
