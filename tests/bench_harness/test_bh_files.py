"""The benchmark is driven by data: every file BENCHMARK.json names is
there, found by name; the peaks table refuses a device it does not know;
the kernels' needed work matches hand-worked shapes."""

import json
import os
import re

import pytest

from benchmark import cells
from benchmark.kernels import int8_scan_rerank as kern

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BJ = cells.benchmark_json()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_benchmark_json_has_exactly_the_contract_keys():
    assert sorted(BJ) == sorted(["command", "paths", "run_seconds", "configs",
                                 "workloads", "end_to_end", "per_layer"])
    assert any(m["name"] == "setup_s" for m in BJ["end_to_end"])
    assert all(0 < m["bound"] <= 0.25 for m in BJ["end_to_end"])


@pytest.mark.parametrize("cfg", BJ["configs"], ids=lambda c: c["name"])
def test_configuration_file_exists_and_states_its_deployment(cfg):
    path = os.path.join(REPO, cfg["file"])
    assert os.path.exists(path) and cfg["file"].startswith("benchmark/")
    with open(path) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"] and body["source"] == cfg["source"]
    assert body["reduced"] == cfg["reduced"]
    for key in ("rows", "dimension", "metric", "space", "search", "serving",
                "guarantees", "limits", "assumed", "rehearsal"):
        assert key in body, key
    assert any(w["config"] == cfg["name"] for w in BJ["workloads"])
    cells.kernel(body["serving"]["kernel"])


@pytest.mark.parametrize("w", BJ["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_its_files(w):
    cell = cells.Cell(w["name"])
    assert cell.traffic["name"] == w["traffic"]
    assert cell.traffic["loop"] in ("closed", "open")
    assert cell.chips in (1, 4) and len(w["why"]) <= 200
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names


@pytest.mark.parametrize("m", BJ["end_to_end"] + BJ["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader_of_its_own(m):
    assert NAME.match(m["name"]) and " " not in m["unit"]
    assert callable(cells.metric_reader(m["name"]))
    for w in m.get("workloads", []):
        assert w in {x["name"] for x in BJ["workloads"]}


def test_unknown_workload_and_unknown_device_are_errors():
    with pytest.raises(KeyError):
        cells.Cell("no.such-cell")
    with pytest.raises(KeyError):
        cells.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        cells.peaks("_source")
    v5e = cells.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9


def test_needed_work_by_hand():
    # 2 query rows, 1000 stored rows of 8 dims, 4 candidates each
    w = kern.needed(rows=2, n=1000, d=8, r=4)
    assert w["flops"] == 2 * 2 * 1000 * 8 + 2 * 2 * 4 * 8  # 32,128
    mirror = 1000 * 8 + 2 * 4 * 1000                       # int8 + 2 f32 cols
    gathered = 2 * 4 * (8 * 4 + 4)
    assert w["bytes"] == mirror + gathered + 2 * 8 * 4      # 16,352
    # the [rows, N] f32 score matrix is NOT needed work
    assert w["bytes"] < 2 * 1000 * 4 + mirror


def test_least_time_says_which_bound_binds():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert kern.least_seconds({"flops": 1000.0, "bytes": 50.0}, peak) == (
        10.0, "compute")
    assert kern.least_seconds({"flops": 100.0, "bytes": 50.0}, peak) == (
        5.0, "memory")
    # the headline shape: a 64-row dispatch over 1M x 128 is bound by
    # reading the mirror once, not by the matrix unit
    v5e = cells.peaks("TPU v5 lite")
    t, bound = kern.least_seconds(kern.needed(64, 1_000_000, 128, 256), v5e)
    # (136,000,000 mirror + 8,454,144 gathered + 32,768 queries) / 819e9
    assert bound == "memory" and t == pytest.approx(144_486_912 / 819e9)
    t, bound = kern.least_seconds(kern.needed(256, 1_000_000, 128, 256), v5e)
    assert bound == "compute"


def test_traffic_files_are_data_only():
    tdir = os.path.join(REPO, "benchmark", "traffic")
    used = {w["traffic"] for w in BJ["workloads"]}
    have = {f[:-5] for f in os.listdir(tdir) if f.endswith(".json")}
    assert used <= have
    assert all(f.endswith(".json") for f in os.listdir(tdir))
