"""Where the benchmark finds what a cell is made of, by name.

`BENCHMARK.json` at the root of the checkout names each cell's
configuration and traffic. Each lives in a file of its own beside this
module, so that a later change adds a cell, a configuration, a traffic
mix or a metric by adding files and edits none:

  configs/<config>.json     the model's sizes, optimizer and mesh as run;
                            its "model" names its architecture
  archs/<model>.py          the architecture: its trainer, record spec,
                            seeded weights, reference replay, operation
                            and byte counts, batch checks and scope names
  traffic/<traffic>.json    the feed's parameters: records, pipeline
                            cores, the tuner's worker budget and cadence
  limits/<cell>.json        the limit of each number `correct` compares
  metrics/<metric>.py       a reader: read(record) -> value or None
  peaks.json                each chip's published peaks, by device_kind
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class LayoutError(LookupError):
    """A cell, file or metric that the benchmark cannot find."""


def _check_name(name: str) -> str:
    if not _NAME.match(name or ""):
        raise LayoutError(f"not a benchmark name: {name!r}")
    return name


def _json(*parts: str) -> Dict:
    path = os.path.join(HERE, *parts)
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise LayoutError(f"missing {os.path.relpath(path, ROOT)}") from None


def benchmark(root: str = ROOT) -> Dict:
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except FileNotFoundError:
        raise LayoutError(f"no BENCHMARK.json in {root}") from None


def config(name: str) -> Dict:
    return _json("configs", _check_name(name) + ".json")


def traffic(name: str) -> Dict:
    return _json("traffic", _check_name(name) + ".json")


def limits(cell: str) -> Dict:
    return _json("limits", _check_name(cell) + ".json")


def peaks(device_kind: str) -> Dict:
    table = _json("peaks.json")
    if device_kind not in table:
        raise LayoutError(f"no peaks for device kind {device_kind!r}: add "
                          f"them, with their source, to peaks.json")
    return table[device_kind]


def cell(name: str, bench: Optional[Dict] = None) -> Dict:
    """The cell's BENCHMARK.json entry with its configuration, traffic
    and limits loaded."""
    bench = bench if bench is not None else benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise LayoutError(f"no workload {name!r} in BENCHMARK.json")
    return {"name": name, "chips": int(entry["chips"]),
            "config": config(entry["config"]),
            "traffic": traffic(entry["traffic"]),
            "limits": limits(name)}


def metrics_for(name: str, trace: bool, bench: Optional[Dict] = None) -> List[Dict]:
    """The metrics the cell reports: its end-to-end ones untraced, its
    per-layer ones traced (a metric with a `workloads` list only in the
    cells it names)."""
    bench = bench if bench is not None else benchmark()
    group = bench["per_layer" if trace else "end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def reader(metric: str) -> Callable[[Dict], Optional[float]]:
    path = os.path.join(HERE, "metrics", _check_name(metric) + ".py")
    if not os.path.exists(path):
        raise LayoutError(f"no reader metrics/{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + metric.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def arch(model: str) -> ModuleType:
    """The module `archs/<model>.py`, loaded once per process. It gives:

      SCOPES                      the train step's named scopes
      SPANS                       (optional) host spans the model adds
      make_trainer(cfg, mesh)     the program's trainer
      record_spec(cfg, traffic, seed)   the records the pipeline makes
      init_params(words, cfg, dtype)    the seeded weights, program layout
      replay(seed, cfg, steps, *, quant, fault, shards, device)
                                  the reference over the checked steps
      train_flops_per_example(cfg)
      scope_work(cfg)             {scope: {"ops", "bytes"}} a step needs
      feed_faults(batches, cfg)   what the fed batches break
    """
    key = "chip_arch:" + _check_name(model)
    if key not in sys.modules:
        path = os.path.join(HERE, "archs", model + ".py")
        if not os.path.exists(path):
            raise LayoutError(f"no architecture archs/{model}.py")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]
