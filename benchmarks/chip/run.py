"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for in BENCHMARK.json. Set-up draws the weights from the seed,
trains the first fed steps through the served loop, and warms every
program the window uses; the window then trains for `--seconds` through
the same loop; after it, the program's state is freed and the float32
reference replays the checked steps. What is particular to the cell's
model comes from its architecture, `archs/<model>.py` (`layout.arch`).
The last line of standard output is
one JSON object (`correct`, `attempted`, `failed`, `metrics`, `device`,
with `--trace 1` also `breakdown`, and last `checks`: each number
compared with its limit), and the last lines of standard error list the
same checks. Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import atexit  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_CHECK_LINES: list = []


def _print_checks() -> None:
    if _CHECK_LINES:
        print("\n".join(_CHECK_LINES), file=sys.stderr, flush=True)


# Registered before anything imports multiprocessing, so that it runs
# after multiprocessing's own exit-time finalizers, whose complaints
# would otherwise follow the compared numbers on standard error.
atexit.register(_print_checks)

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
# the persistent compilation cache: the one JAX is given, else a fixed
# directory inside the checkout (the path is part of the cache's key)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(HERE, "out", "jax_cache"))

import layout  # noqa: E402


def require_tpu(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"run.py: no TPU (JAX reports {devices[0].platform!r} "
                 f"devices); nothing was run")
    if len(devices) < chips:
        sys.exit(f"run.py: the cell needs {chips} TPU chips, JAX reports "
                 f"{len(devices)}")
    return devices[:chips]


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devices)


def measure(argv=None, *, devices=None, cell=None) -> dict:
    """One run; returns the result line's object. Tests hand in the
    devices (skipping the look for a chip) and a cell cut to their size."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = layout.benchmark()
    cell = cell if cell is not None else layout.cell(args.workload, bench)
    wanted = layout.metrics_for(cell["name"], bool(args.trace), bench)
    readers = {m["name"]: layout.reader(m["name"]) for m in wanted}

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # a compiled step's op_names (its scopes) are part of its cache key:
    # a warm cache never serves another commit's scopes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    devices = devices if devices is not None else require_tpu(cell["chips"])
    kind = devices[0].device_kind
    peaks = layout.peaks(kind) if devices[0].platform == "tpu" else None

    import harness
    import spans
    import trace as trace_mod
    cfg = cell["config"]
    arch = layout.arch(cfg["model"])
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(time.perf_counter())
        if event == "/jax/core/compile/backend_compile_duration" else None)
    trace_dir = os.path.join(HERE, "out", "trace") if args.trace else None
    trainer = harness.make_trainer(cfg, devices)
    run = harness.drive(trainer, cell, args.seed, args.seconds, trace_dir)
    if run.t1 is None:
        sys.exit("run.py: the window never closed")
    peak = peak_bytes(devices)
    program = run.program_readings()
    run.free()
    del trainer
    harness.stop_tracker()

    ref = harness.reference_readings(run, cell, device=devices[0])
    numbers = harness.compared(run, program, ref)
    limits = cell["limits"]
    checks = {k: {"value": v, "limit": float(limits[k]["limit"])}
              for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    window_s = run.t1 - run.t0
    record = {
        "setup_s": run.t0 - _T0, "window_s": window_s, "steps": run.steps,
        "batch": cfg["batch"], "chips": cell["chips"],
        "examples": run.steps * cfg["batch"],
        "feed_wait_s": run.feed_wait_s,
        "pipeline_cpu_s": (run.cpu1 - run.cpu0
                           if run.cpu0 is not None and run.cpu1 is not None else None),
        "peak_bytes": peak, "peaks": peaks,
        "flops_per_example": arch.train_flops_per_example(cfg),
        "config": cfg, "scope_work": arch.scope_work(cfg),
        "trace": None, "spans": None,
    }
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": run.steps, "failed": run.nonfinite}
    if args.trace:
        record["trace"] = trace_mod.reduce(trace_mod.extract(trace_dir))
        record["spans"] = spans.window(trace_dir, arch)
        device.update(busy_s=record["trace"]["busy_s"],
                      window_s=record["trace"]["window_s"])
        out["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
    metrics = {}
    for m in wanted:
        value = readers[m["name"]](record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out.update(metrics=metrics, device=device)
    inside = sum(run.t0 <= t <= run.t1 for t in compiles)
    print(f"run.py: {run.steps} steps in {window_s:.3f} s; compiles inside "
          f"the window: {inside}; worst leaves "
          f"{harness.check.worst_leaves(program, ref)}",
          file=sys.stderr)
    if args.trace:
        print(f"run.py: idle by host span {record['trace']['idle_by_span']}; "
              f"train step on device {record['trace']['step_device_s']} s",
              file=sys.stderr)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    out = measure(argv)
    print(json.dumps(out), flush=True)
    _CHECK_LINES[:] = [f"check {k} {c['value']!r} limit {c['limit']!r}"
                       for k, c in out["checks"].items()]
    return 0


if __name__ == "__main__":
    sys.exit(main())
