"""The program's own spans, counters and named scopes in a traced window.

`trace.py` reads the window and the device's busy time. This module
reads what the program itself writes into the same profiler trace:

  host    the spans and counters of `repro.common.tracing` and the
          loop's `StepTraceAnnotation` ("train"), on the loop's thread:
          the host line that holds the harness's `bench_window`. The
          served loop's and the tuner tick's span names are every
          model's (`SPANS`); a model's own host spans come from its
          architecture (`archs/<model>.SPANS`). Every other host event
          that carries an `n` stat is a counter (`tracing.count`);
  device  every operation of a `jit_train_step` call, put down to the
          innermost `jax.named_scope` of its instruction, of those the
          traced cell's architecture names (`archs/<model>.SCOPES`). The v5e's op
          events carry no metadata, so the scope comes from
          `metadata={op_name=...}` in the compiled module, which the
          trace's `/host:metadata` plane holds as a serialized HloProto
          keyed by the module's name; a fusion whose own metadata names
          no scope takes the first it calls that does.

A span that overlaps a later one of its name on the same line is an
outer copy (the harness writes the loop's four names too) and is not
counted. Each instant of an idle gap goes to the innermost span over it
(the last to start), and each instant of a step call to the innermost
operation, so the parts add up to the whole.

`extract` reads the `.xplane.pb` into plain lists, `reduce` works on
those, so the reduction can be checked without a chip; `window()` does
both for `run.py`, which hands the result to the metric readers.

    python3 benchmarks/chip/spans.py [trace_dir [model]]   # prints the reduction
"""
from __future__ import annotations

import glob
import heapq
import json
import os
import re
import sys
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(HERE, "out", "trace")    # where run.py traces
WINDOW = "bench_window"
STEP = "train"                  # the loop's StepTraceAnnotation
STEP_MODULE = "jit_train_step"
LOOP_SPANS = ("feed_wait", "step_dispatch", "host_loop", "loss_read",
              "tune_sync", "tuner_tick")
TICK_SPANS = ("tick_measure", "tick_observe", "tick_propose", "tick_apply",
              "pipe_stats", "rss_scan", "worker_start", "worker_retire")
SPANS = LOOP_SPANS + TICK_SPANS
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^%(\S+) = ")


def scope_pattern(scopes: Sequence[str]) -> "re.Pattern":
    """A scope as one part of an op_name: "/embedding_lookup/" and the
    transformed "jvp(embedding_lookup)" both count."""
    if not scopes:
        return re.compile(r"(?!)")
    return re.compile(r"(?:^|[/(])(" + "|".join(map(re.escape, scopes))
                      + r")(?=$|[/)])")

Event = Tuple[str, float, float]


# ------------------------------------------------------------- protobuf --
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None):
    """(field, value) of one protobuf message in buf[lo:hi]; the value of
    a length-delimited field is its (lo, hi) slice."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _sub(buf: bytes, span: Tuple[int, int], field: int) -> List:
    return [v for f, v in _fields(buf, *span) if f == field]


def _str(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _ids(buf: bytes, values: List) -> List[int]:
    """A repeated int64 field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, tuple):
            i = v[0]
            while i < v[1]:
                x, i = _varint(buf, i)
                out.append(x)
        else:
            out.append(v)
    return out


def module_scopes(buf: bytes, hlo_proto: Tuple[int, int],
                  pattern: "re.Pattern") -> Dict[str, str]:
    """{instruction name: innermost scope} of one serialized HloProto
    (HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto name 1, instructions 2, id 5; HloInstructionProto
    name 1, metadata 7, called_computation_ids 38; OpMetadata.op_name 2)."""
    module = _sub(buf, hlo_proto, 1)[0]
    comps: Dict[int, List[str]] = {}
    own: Dict[str, str] = {}
    calls: Dict[str, List[int]] = {}
    for comp in _sub(buf, module, 3):
        cid = (_sub(buf, comp, 5) or [None])[0]
        names = []
        for ins in _sub(buf, comp, 2):
            name = _str(buf, _sub(buf, ins, 1)[0])
            names.append(name)
            meta = _sub(buf, ins, 7)
            op_name = _sub(buf, meta[0], 2) if meta else []
            found = pattern.findall(_str(buf, op_name[0])) if op_name else []
            if found:
                own[name] = found[-1]
            calls[name] = _ids(buf, _sub(buf, ins, 38))
        comps[cid] = names

    def scope(name: str, seen: set) -> Optional[str]:
        if name in own:
            return own[name]
        for cid in calls.get(name, ()):
            if cid in seen or cid not in comps:
                continue
            seen.add(cid)
            for inner in reversed(comps[cid]):       # the root is last
                s = scope(inner, seen)
                if s:
                    return s
        return None

    out = {}
    for name in calls:
        s = scope(name, set())
        if s:
            out[name] = s
    return out


def hlo_scopes(path: str, scopes: Sequence[str]) -> Dict[str, Dict[str, str]]:
    """{module name: module_scopes} of every train-step module in the
    trace's metadata plane (XSpace.planes = 1; XPlane name 2,
    event_metadata 4 as map entries of key 1 and value 2; XEventMetadata
    name 2, stats 5; XStat.bytes_value 6)."""
    with open(path, "rb") as fh:
        buf = fh.read()
    out = {}
    pattern = scope_pattern(scopes)
    for field, plane in _fields(buf):
        if field != 1:
            continue
        name = next((_str(buf, v) for f, v in _fields(buf, *plane) if f == 2), "")
        if name != "/host:metadata":
            continue
        for entry in _sub(buf, plane, 4):
            for meta in _sub(buf, entry, 2):
                mod = _sub(buf, meta, 2)
                mod = _str(buf, mod[0]) if mod else ""
                if not mod.startswith(STEP_MODULE):
                    continue
                for stat in _sub(buf, meta, 5):
                    for blob in _sub(buf, stat, 6):
                        out[mod] = module_scopes(buf, blob, pattern)
    return out


# -------------------------------------------------------------- extract --
def extract(trace_dir: str, scopes: Sequence[str] = (),
            spans: Sequence[str] = ()) -> Dict:
    """The newest trace under `trace_dir` as {"host": {line: [[name,
    start_ns, duration_ns, n], ...]}, "devices": {plane: {"ops":
    [[instruction, start_ns, duration_ns], ...], "modules": [...]}},
    "scopes": {module: {instruction: scope}}}; host events only of the
    span names read here (`SPANS` and the model's `spans`) and the
    counters (events with an `n` stat), `n` a counter's increase (1 for
    a span); `scopes` the model's named scopes."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    wanted = set(SPANS) | set(spans) | {WINDOW, STEP}
    host: Dict[str, List] = {}
    devices: Dict[str, Dict[str, List]] = {}
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        m = _INSTR.match(e.name)
                        d["ops"].append([m.group(1) if m else e.name,
                                         float(e.start_ns), float(e.duration_ns)])
                elif line.name == "XLA Modules":
                    d["modules"].extend([e.name, float(e.start_ns), float(e.duration_ns)]
                                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                kept = []
                for e in line.events:
                    if e.name in wanted:
                        kept.append([e.name, float(e.start_ns), float(e.duration_ns), 1])
                        continue
                    n = dict(e.stats).get("n")
                    if isinstance(n, int):
                        kept.append([e.name, float(e.start_ns), 0.0, n])
                if kept:
                    host[f"{plane.name}#{k}:{line.name}"] = kept
    return {"host": host, "devices": devices,
            "scopes": hlo_scopes(paths[-1], scopes)}


# --------------------------------------------------------------- reduce --
def innermost(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut into pieces, each named by the innermost event over
    it (the last to start of those covering it); pieces no event covers
    are left out."""
    evs = sorted((max(s, lo), min(s + d, hi), name) for name, s, d in events
                 if s < hi and s + d > lo)
    cuts = sorted({lo, hi} | {t for s, e, _ in evs for t in (s, e)})
    out: List[Tuple[float, float, str]] = []
    active: List[Tuple[float, float, str]] = []      # (-start, end, name)
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(evs) and evs[k][0] <= a:
            heapq.heappush(active, (-evs[k][0], evs[k][1], evs[k][2]))
            k += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        if active:
            name = active[0][2]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def _busy(ops: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    spans = sorted((max(s, lo), min(s + d, hi)) for _, s, d in ops
                   if s < hi and s + d > lo)
    out: List[Tuple[float, float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _last_of_name(events: Sequence[Event]) -> List[Event]:
    """Drop each span that overlaps a later span of its name."""
    by_name: Dict[str, List[Event]] = defaultdict(list)
    for ev in events:
        by_name[ev[0]].append(ev)
    kept = []
    for evs in by_name.values():
        evs.sort(key=lambda ev: ev[1])
        kept += [ev for ev, nxt in zip(evs, evs[1:] + [None])
                 if nxt is None or nxt[1] >= ev[1] + ev[2]]
    return sorted(kept, key=lambda ev: ev[1])


def _holds(outer: Event, inner: Event) -> bool:
    return (outer is not inner and outer[1] <= inner[1]
            and inner[1] + inner[2] <= outer[1] + outer[2])


def _pieces_in(pieces, starts, s: float, e: float):
    """The parts of `innermost` pieces inside [s, e)."""
    k = max(0, bisect_right(starts, s) - 1)
    while k < len(pieces) and pieces[k][0] < e:
        a, b, name = pieces[k]
        lo, hi = max(a, s), min(b, e)
        if hi > lo:
            yield lo, hi, name
        k += 1


def _host_reduce(lines: Dict[str, List], w0: float, w1: float, loop: str,
                 first_ops: Sequence[Event], top: int, names: set) -> Dict:
    inside = lambda ev: ev[1] >= w0 and ev[1] + ev[2] <= w1
    spans = _last_of_name([tuple(ev[:3]) for ev in lines[loop]
                           if ev[0] in names and inside(ev)])
    table: Dict[str, Dict[str, float]] = {}
    for name, _, d in spans:
        t = table.setdefault(name, {"n": 0, "s": 0.0})
        t["n"] += 1
        t["s"] += d * 1e-9
    counters: Dict[str, int] = defaultdict(int)
    elsewhere: Dict[str, float] = defaultdict(float)
    for key, evs in lines.items():
        for name, s, d, n in evs:
            if name in names or name in (WINDOW, STEP):
                if key != loop and name in names and inside((name, s, d)):
                    elsewhere[name] += d * 1e-9
            elif w0 <= s <= w1:
                counters[name] += n
    # the tick by part: its direct children, and its own time
    ticks = [ev for ev in spans if ev[0] == "tuner_tick"]
    split: Dict[str, float] = defaultdict(float)
    for _, ts, td in ticks:
        within = [ev for ev in spans if ev[0] != "tuner_tick"
                  and ev[1] >= ts and ev[1] + ev[2] <= ts + td]
        children = [c for c in within if not any(_holds(o, c) for o in within)]
        for name, _, d in children:
            split[name] += d * 1e-9
        split["self"] += (td - sum(d for _, _, d in children)) * 1e-9
    # idle gaps of the first device, by the innermost span over them
    pieces = innermost(spans + [tuple(ev[:3]) for ev in lines[loop]
                                if ev[0] == STEP and inside(ev)], w0, w1)
    starts = [p[0] for p in pieces]
    by_span: Dict[str, float] = defaultdict(float)
    gaps = []
    t = w0
    for s, e in _busy(first_ops, w0, w1) + [(w1, w1)]:
        if s > t:
            parts: Dict[str, float] = defaultdict(float)
            for a, b, name in _pieces_in(pieces, starts, t, s):
                parts[name] += b - a
            parts["unattributed"] += (s - t) - sum(parts.values())
            for name, x in parts.items():
                by_span[name] += x * 1e-9
            gaps.append((s - t, max(parts, key=parts.get)))
        t = max(t, e)
    gaps.sort(reverse=True)
    return {
        "steps": sum(1 for ev in lines[loop] if ev[0] == STEP and inside(ev)),
        "spans": dict(sorted(table.items())),
        "spans_elsewhere_s": dict(sorted(elsewhere.items())),
        "counters": dict(sorted(counters.items())),
        "ticks": len(ticks),
        "tick_split_s": dict(sorted(split.items())),
        "tick_s": sum(d for _, _, d in ticks) * 1e-9,
        "idle_by_span_s": {k: v for k, v in sorted(by_span.items()) if v > 0},
        "idle_gaps": [[name, length * 1e-9] for length, name in gaps[:top]],
    }


def _device_reduce(devices: Dict, scopes: Dict[str, Dict[str, str]],
                   w0: float, w1: float) -> Dict:
    """Per call of the train step, averaged over the chips: its module
    time and that time by the innermost operation's scope ("unscoped":
    an operation under none; "between_ops": no operation running)."""
    total: Dict[str, float] = defaultdict(float)
    step_s, calls = 0.0, 0
    for plane in devices.values():
        ops = sorted((tuple(o) for o in plane["ops"]), key=lambda o: o[1])
        starts = [o[1] for o in ops]
        for mod, s, d in plane["modules"]:
            if not mod.startswith(STEP_MODULE) or s < w0 or s + d > w1:
                continue
            names = scopes.get(mod, {})
            calls += 1
            step_s += d
            lo = bisect_right(starts, s) - 1
            # an op starts inside the call (or just before, if it spans it)
            within = [o for o in ops[max(0, lo):bisect_right(starts, s + d)]
                      if o[1] + o[2] > s]
            covered = 0.0
            for a, b, instr in innermost(within, s, s + d):
                total[names.get(instr, "unscoped")] += b - a
                covered += b - a
            total["between_ops"] += d - covered
    if not calls:
        return {"step_calls": 0, "step_ms": None, "scope_ms": {}}
    return {"step_calls": calls // len(devices),
            "step_ms": step_s / calls * 1e-6,
            "scope_ms": {k: v / calls * 1e-6 for k, v in sorted(total.items())}}


def reduce(ev: Dict, top: int = 10, spans: Sequence[str] = ()) -> Dict:
    """The window's program spans (`SPANS` and the model's `spans`),
    counters, tick split and idle gaps (host), and the train step's
    device time by scope. "program" is false where the loop wrote no
    step annotation: a program without these spans, whose numbers are
    then not read."""
    lines = ev["host"]
    windows = [(key, e) for key, evs in lines.items() for e in evs if e[0] == WINDOW]
    if not windows or not ev["devices"]:
        raise ValueError("trace holds no window span or no device plane")
    loop, (_, w0, wd, _) = windows[-1]
    w1 = w0 + wd
    first = sorted(ev["devices"], key=lambda p: int(_DEVICE.match(p).group(1)))[0]
    out = {"window_s": wd * 1e-9,
           "program": any(e[0] == STEP for e in lines[loop])}
    out.update(_host_reduce(lines, w0, w1, loop,
                            [tuple(o) for o in ev["devices"][first]["ops"]], top,
                            set(SPANS) | set(spans)))
    out.update(_device_reduce(ev["devices"], ev.get("scopes", {}), w0, w1))
    return out


def window(trace_dir: str = TRACE_DIR, arch=None) -> Optional[Dict]:
    """`reduce` of the newest trace under `trace_dir`, with the scopes
    and host spans of `arch` (an `archs/<model>` module); None where
    there is no trace or the program wrote no spans into it."""
    if not glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True):
        return None
    scopes = getattr(arch, "SCOPES", ())
    extra = getattr(arch, "SPANS", ())
    red = reduce(extract(trace_dir, scopes, extra), spans=extra)
    return red if red["program"] else None


def per_tick(red: Optional[Dict], seconds: float) -> Optional[float]:
    """Seconds (or a count) over the window's ticks; None without ticks."""
    if red is None or not red["ticks"]:
        return None
    return seconds / red["ticks"]


def roofline_pct(rec: Dict, scope: str) -> Optional[float]:
    """The share of its roofline that a scope's device time per step
    reaches: the least time the chip could take for the operations and
    bytes the architecture counts for it (`scope_work`), the larger of
    ops over the bf16 peak and bytes over the HBM peak, over the scope's
    measured time, in %. None where the scope was not measured or not
    counted, or the device has no peaks."""
    red, work, peaks = rec["spans"], rec["scope_work"].get(scope), rec["peaks"]
    ms = red and red["scope_ms"].get(scope)
    if not ms or work is None or peaks is None:
        return None
    least = max(work["ops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)


if __name__ == "__main__":
    sys.path.insert(0, HERE)
    import layout
    model = layout.arch(sys.argv[2]) if len(sys.argv) > 2 else None
    print(json.dumps(window(sys.argv[1] if len(sys.argv) > 1 else TRACE_DIR, model),
                     indent=1))
