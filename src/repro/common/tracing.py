"""Spans and counters of the program, on the profiler's clock.

    with tracing.span("tuner_tick"):
        session.step()
    tracing.count("workers_started")
    before = tracing.snapshot()
    ...
    tracing.since(before)   # {"tuner_tick": {"n": 2, "s": 0.41}, ...}

A span enters a `jax.profiler.TraceAnnotation` of its name, so while a
profiler trace is active it lands on the trace's host plane, on the
same clock as the device's `XLA Ops`; with no trace active the
annotation records nothing. A counter marks each increase on the trace
as an instant event of its name carrying `n`. Both also add to one
in-process table keyed by name (a span its count and seconds, a counter
its count), which `snapshot()` copies for callers to difference: a log
line, a measured window, `FeedRun`. The profiler's trace is the record
of single spans; nothing here exports.

Spans run on the train loop's thread and on the pipeline's RSS sampler
thread, so the table sits behind a lock that guards only its update.
A profiler trace needs JAX, so a process that never imported it (a
pipeline driven without a trainer) skips the annotation and keeps only
the table.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Dict, List

_LOCK = threading.Lock()
_TABLE: Dict[str, List[float]] = {}     # name -> [count, seconds]


def _annotation(name: str, **stats):
    jax = sys.modules.get("jax")
    return None if jax is None else jax.profiler.TraceAnnotation(name, **stats)


def _add(name: str, n: int, s: float) -> None:
    with _LOCK:
        entry = _TABLE.get(name)
        if entry is None:
            _TABLE[name] = [n, s]
        else:
            entry[0] += n
            entry[1] += s


class span:
    """Context manager: one span of `name` (see the module docstring)."""

    __slots__ = ("name", "_ann", "_t0")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "span":
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _add(self.name, 1, dt)


def count(name: str, n: int = 1) -> None:
    """Add `n` to the counter `name`."""
    ann = _annotation(name, n=n)
    if ann is not None:
        with ann:
            pass
    _add(name, n, 0.0)


def snapshot() -> Dict[str, Dict[str, float]]:
    """{name: {"n": count, "s": seconds}} so far (`s` is 0 for a
    counter)."""
    with _LOCK:
        return {k: {"n": v[0], "s": v[1]} for k, v in _TABLE.items()}


def since(before: Dict[str, Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    """What was added to the table since `before`, a `snapshot()`."""
    out = {}
    for k, v in snapshot().items():
        b = before.get(k, {"n": 0, "s": 0.0})
        if v["n"] != b["n"]:
            out[k] = {"n": v["n"] - b["n"], "s": v["s"] - b["s"]}
    return out
