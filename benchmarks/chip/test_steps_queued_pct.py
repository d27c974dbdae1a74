"""The reader of `steps_queued_pct`, which needs no chip.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip
"""
from __future__ import annotations

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE]

import layout  # noqa: E402

READ = layout.reader("steps_queued_pct")


def _record(steps, counters):
    return {"spans": {"steps": steps, "counters": counters}}


def test_share_of_the_windows_steps_that_queued():
    assert READ(_record(40, {"steps_queued": 30, "workers_started": 5})) == 75.0
    assert READ(_record(40, {"steps_queued": 40})) == 100.0


@pytest.mark.parametrize("rec", [{"spans": None}, _record(0, {}),
                                 _record(40, {"ticks_outlasting_step": 3})])
def test_nothing_where_the_window_holds_no_count(rec):
    """Untraced, no steps, or a program that does not count queued steps:
    the metric is left out, not read as 0."""
    assert READ(rec) is None


def test_reported_in_both_accepted_cells():
    bench = layout.benchmark()
    for w in bench["workloads"]:
        names = [m["name"] for m in layout.metrics_for(w["name"], True, bench)]
        assert "steps_queued_pct" in names, w["name"]
