"""Tests of `spans.py`, the reduction of the program's own spans,
counters and named scopes, that need no chip.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip

By hand: gaps go to the innermost span, operations to their scope, the
parts add up to the whole, the harness's copies of the loop's names are
not counted, and every other host event with a count is a counter. Then
on a small trace recorded on a v5e (two steps and one tuner tick of a
`dlrm-criteo-1chip.cores-all` window, the window span cut to them), and
the HloProto decoder on a hand-encoded module, with the scope names of
the architecture it is given.
"""
from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import layout  # noqa: E402
import spans  # noqa: E402

NEW = ("tuner_tick_ms", "tick_rss_scan_ms", "worker_starts_per_tick",
       "embedding_lookup_device_ms", "table_update_device_ms",
       "ticks_outlasting_step_pct", "embedding_lookup_roofline",
       "table_update_roofline")
SCOPES = layout.arch("dlrm").SCOPES


def _record(red) -> dict:
    """What run.py hands the readers of a traced v5e run of the cell."""
    cfg = layout.config("dlrm-criteo-1chip")
    return {"trace": {}, "spans": red, "peaks": layout.peaks("TPU v5 lite"),
            "config": cfg, "scope_work": layout.arch("dlrm").scope_work(cfg)}


def _hand_trace() -> dict:
    """A 100-ns window: two steps, the second with a tick; the harness's
    outer copies of `feed_wait` and `tuner_tick` overlap the program's."""
    loop = [
        ["bench_window", 0, 100, 1],
        ["train", 0, 40, 1], ["train", 40, 60, 1],
        ["feed_wait", 0, 10, 1], ["feed_wait", 2, 6, 1],        # harness, program
        ["step_dispatch", 10, 5, 1], ["host_loop", 15, 20, 1],
        ["loss_read", 15, 18, 1],
        ["feed_wait", 40, 2, 1], ["step_dispatch", 42, 3, 1],
        ["host_loop", 45, 5, 1], ["loss_read", 45, 4, 1],
        ["tuner_tick", 49, 51, 1], ["tuner_tick", 50, 45, 1],   # harness, program
        ["tick_measure", 50, 20, 1], ["rss_scan", 52, 10, 1],
        ["tick_apply", 75, 15, 1], ["worker_start", 76, 12, 1],
        ["rss_scan", 85, 2, 1], ["workers_started", 88, 0, 1],
        ["rss_pids_read", 60, 0, 3], ["rss_pids_read", 86, 0, 1],
        ["ticks_outlasting_step", 94, 0, 1],
    ]
    sampler = [["rss_scan", 20, 30, 1], ["rss_pids_read", 49, 0, 3]]
    ops = [["dot.1", 10, 30], ["gather.2", 12, 10], ["fusion.3", 25, 10],
           ["copy.4", 42, 6]]
    return {"host": {"/host:CPU#0:main": loop, "/host:CPU#1:sampler": sampler},
            "devices": {"/device:TPU:0": {
                "ops": ops,
                "modules": [["jit_train_step(1)", 10, 32], ["jit_other(2)", 42, 6]]}},
            "scopes": {"jit_train_step(1)": {"dot.1": "top_mlp",
                                             "gather.2": "embedding_lookup",
                                             "fusion.3": "table_update"}}}


def test_spans_reduction_by_hand():
    r = spans.reduce(_hand_trace())
    assert r["program"] and r["steps"] == 2 and r["ticks"] == 1
    # the harness's overlapping copies are not counted
    assert r["spans"]["feed_wait"] == {"n": 2, "s": pytest.approx(8e-9)}
    assert r["spans"]["tuner_tick"] == {"n": 1, "s": pytest.approx(45e-9)}
    assert r["spans"]["rss_scan"] == {"n": 2, "s": pytest.approx(12e-9)}
    assert r["spans_elsewhere_s"] == {"rss_scan": pytest.approx(30e-9)}
    assert r["counters"] == {"rss_pids_read": 7, "workers_started": 1,
                             "ticks_outlasting_step": 1}
    assert layout.reader("ticks_outlasting_step_pct")(_record(r)) == 100.0
    # the tick: its direct children and its own time add up to it
    assert r["tick_split_s"] == {"tick_measure": pytest.approx(20e-9),
                                 "tick_apply": pytest.approx(15e-9),
                                 "self": pytest.approx(10e-9)}
    assert sum(r["tick_split_s"].values()) == pytest.approx(r["tick_s"])
    # idle: [0,10), [40,42) and [48,100), each instant to the innermost
    # span; instants under no loop span go to the step annotation
    assert r["idle_by_span_s"] == pytest.approx({
        "feed_wait": 8e-9, "train": 9e-9, "loss_read": 1e-9,
        "host_loop": 1e-9, "tick_measure": 10e-9, "rss_scan": 12e-9,
        "tuner_tick": 10e-9, "tick_apply": 3e-9, "worker_start": 10e-9})
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(
        (10 + 2 + 52) * 1e-9)
    assert r["idle_gaps"][0] == ["rss_scan", pytest.approx(52e-9)]
    # the step: [10,42) by innermost op; the other module is left out
    assert r["step_calls"] == 1 and r["step_ms"] == pytest.approx(32e-6)
    assert r["scope_ms"] == pytest.approx({
        "top_mlp": 10e-6, "embedding_lookup": 10e-6, "table_update": 10e-6,
        "between_ops": 2e-6})
    assert sum(r["scope_ms"].values()) == pytest.approx(r["step_ms"])


def test_a_trace_without_the_programs_spans_reads_nothing(monkeypatch, tmp_path):
    """The harness's own spans alone (a program without tracing) give no
    program reading, and every new reader returns None."""
    ev = _hand_trace()
    loop = ev["host"]["/host:CPU#0:main"]
    ev["host"]["/host:CPU#0:main"] = [e for e in loop if e[0] != "train"]
    assert spans.reduce(ev)["program"] is False
    path = tmp_path / "plugins" / "profile" / "x" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    monkeypatch.setattr(spans, "extract", lambda d, scopes, extra: ev)
    assert spans.window(str(tmp_path), layout.arch("dlrm")) is None
    for name in NEW:
        assert layout.reader(name)(_record(None)) is None
        assert layout.reader(name)({**_record(None), "trace": None}) is None


def _recorded() -> dict:
    with open(os.path.join(HERE, "testdata", "v5e_program_spans.json"),
              encoding="utf-8") as fh:
        return json.load(fh)


def test_spans_reduction_of_a_recorded_trace():
    r = spans.reduce(_recorded())
    assert r["program"] and r["steps"] == 2 and r["ticks"] == 1
    for name in ("feed_wait", "step_dispatch", "host_loop", "loss_read"):
        assert r["spans"][name]["n"] == 2, name
    assert r["spans"]["tune_sync"]["n"] == r["spans"]["tick_measure"]["n"] == 1
    assert r["counters"]["workers_started"] == r["spans"]["worker_start"]["n"] > 0
    assert sum(r["tick_split_s"].values()) == pytest.approx(r["tick_s"], rel=1e-9)
    assert set(r["tick_split_s"]) <= {"tick_measure", "tick_observe",
                                      "tick_propose", "tick_apply", "self"}
    # every idle instant of the window is put down to a span
    ev = _recorded()
    ops = [tuple(o) for o in ev["devices"]["/device:TPU:0"]["ops"]]
    idle = r["window_s"] - sum(e - s for s, e in spans._busy(ops, *_window(ev))) * 1e-9
    assert sum(r["idle_by_span_s"].values()) == pytest.approx(idle, rel=1e-9)
    assert r["idle_by_span_s"].get("unattributed", 0.0) < 1e-3 * idle
    assert {n for n, _ in r["idle_gaps"]} <= set(spans.SPANS) | {"train"}
    # the step's time by scope adds up to the module's
    assert r["step_calls"] == 2
    assert sum(r["scope_ms"].values()) == pytest.approx(r["step_ms"], rel=0.01)
    assert r["scope_ms"]["unscoped"] < 0.1 * r["step_ms"]
    assert 0 < r["scope_ms"]["embedding_lookup"] + r["scope_ms"]["table_update"] \
        < r["step_ms"]
    got = {name: layout.reader(name)(_record(r)) for name in NEW}
    assert got["tuner_tick_ms"] == pytest.approx(1e3 * r["tick_s"])
    assert got["worker_starts_per_tick"] == r["counters"]["workers_started"]
    assert got["embedding_lookup_device_ms"] == r["scope_ms"]["embedding_lookup"]
    assert got["table_update_device_ms"] == r["scope_ms"]["table_update"]
    assert 0 < got["tick_rss_scan_ms"] < got["tuner_tick_ms"]
    # this trace predates the counter: no tick outlasted its step
    assert got["ticks_outlasting_step_pct"] == 0.0
    for name in ("embedding_lookup_roofline", "table_update_roofline"):
        assert 0 < got[name] < 100, name


def _window(ev):
    (_, s, d, _), = [e for evs in ev["host"].values() for e in evs
                     if e[0] == spans.WINDOW]
    return s, s + d


# ---------------------------------------------------------- HloProto --
def _key(field: int, wire: int) -> bytes:
    return _varint(field << 3 | wire)


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _msg(*fields) -> bytes:
    out = b""
    for field, value in fields:
        if isinstance(value, int):
            out += _key(field, 0) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _key(field, 2) + _varint(len(value)) + value
    return out


def _instr(name: str, op_name: str = "", calls=()) -> bytes:
    fields = [(1, name)]
    if op_name:
        fields.append((7, _msg((1, "x"), (2, op_name))))
    if calls:
        fields.append((38, b"".join(_varint(c) for c in calls)))   # packed
    return _msg(*fields)


def test_module_scopes_from_a_hand_encoded_hlo_proto(tmp_path):
    fused = _msg((1, "fused_computation"), (5, 7),
                 (2, _instr("param_0")), (2, _instr("add.1", "add")),
                 (2, _instr("scatter-add.2",
                            "jit(train_step)/transpose(jvp(embedding_lookup))/scatter-add")))
    entry = _msg((1, "main"), (5, 1),
                 (2, _instr("fusion.4", calls=[7])),
                 (2, _instr("while.2", "jit(train_step)/optimizer_update/table_update/while")),
                 (2, _instr("reduce.3", "jit(train_step)/optimizer_update/reduce_sum")),
                 (2, _instr("copy.5", "jit(train_step)/copy")))
    proto = _msg((1, _msg((1, "jit_train_step"), (3, entry), (3, fused))))
    scopes = spans.module_scopes(proto, (0, len(proto)), spans.scope_pattern(SCOPES))
    assert scopes == {"fusion.4": "embedding_lookup", "while.2": "table_update",
                      "reduce.3": "optimizer_update",
                      "scatter-add.2": "embedding_lookup"}
    # and found in an XSpace's metadata plane, keyed by the module's name
    meta = _msg((1, 5), (2, "jit_train_step(5)"), (5, _msg((1, 1), (6, proto))))
    plane = _msg((2, "/host:metadata"), (4, _msg((1, 5), (2, meta))))
    space = _msg((1, _msg((2, "/host:CPU"))), (1, plane))
    path = tmp_path / "h.xplane.pb"
    path.write_bytes(space)
    assert spans.hlo_scopes(str(path), SCOPES) == {"jit_train_step(5)": scopes}
    # another architecture's scopes are found by its names alone
    assert spans.hlo_scopes(str(path), ("table_update", "scatter-add")) == {
        "jit_train_step(5)": {"fusion.4": "scatter-add", "while.2": "table_update",
                              "scatter-add.2": "scatter-add"}}
