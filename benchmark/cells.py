"""A cell, found by name: BENCHMARK.json names a configuration and a
traffic mix; every file that belongs to them is found by that name, so a
later PR adds a cell, a configuration, a mix, a kernel or a per-layer
metric by adding files and entries and editing none."""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_json() -> dict:
    return _load(os.path.join(REPO, "BENCHMARK.json"))


def peaks(device_kind: str) -> dict:
    table = _load(os.path.join(HERE, "peaks.json"))
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json")
    return table[device_kind]


def kernel(name: str):
    return importlib.import_module(f"benchmark.kernels.{name}")


def metric_reader(name: str):
    """The reader of a metric: benchmark/metrics/<name>.py, with '.' and
    '-' read as '_' (a module's name cannot hold them). A variant named
    `<base>.<variant>` (the same quantity in cells that report another
    end-to-end metric) is read by <base>'s reader unless it has one of its
    own."""
    for candidate in (name, name.split(".", 1)[0]):
        mod = candidate.replace(".", "_").replace("-", "_")
        try:
            return importlib.import_module(f"benchmark.metrics.{mod}").read
        except ModuleNotFoundError as e:
            if e.name != f"benchmark.metrics.{mod}":
                raise
    raise KeyError(f"no reader benchmark/metrics/ for metric {name!r}")


class Cell:
    def __init__(self, workload: str):
        bj = benchmark_json()
        cells = {w["name"]: w for w in bj["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(has {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bj["configs"]}[self.entry["config"]]
        self.config = _load(os.path.join(REPO, cfg_entry["file"]))
        self.traffic = _load(os.path.join(
            HERE, "traffic", f"{self.entry['traffic']}.json"))
        self.end_to_end = [m for m in bj["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        # a per-layer metric with no `workloads` key belongs to every cell
        # that reports the end-to-end metric it moves
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bj["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]
