"""What PR 34 added to the benchmark for `gist1m-rabitq.b64x4-closed`:
the cell and its configuration found by name, the kernel file's needed
work by hand, the three readers on a hand-made one-chip trace whose
operation names are the chip's own (copied from PR 34's traced
microbenchmark at 1,000,448 x 960, durations rounded to the
microsecond), and the rehearsal of the cell on the CPU.

The trace: a 30 ms window, two 64-row dispatches of
`jit_binary_refine_rerank` of 9,000,000 ns each: stage 0's score fusion
with the unpack inside it 2,543,000, the block maxima 339,000, its
selection (three sorts 331,000 + 352,000 + 2,366,000 around the gather
of the chosen blocks 377,000), stage 1 (the int8 super-rows' gather
927,000, the two columns' 234,000 each, the product 107,000,
`take_along_axis` 166,000), stage 2 (the raw super-rows' gather
613,000, the norms' 116,000, the exact product 181,000); the rest of a
dispatch is small operations and gaps.
"""

import json
import os
import subprocess
import sys
from collections import namedtuple

import numpy as np
import pytest

from benchmark import cells, spans, trace
from benchmark.kernels import binary_refine_rerank as kern
from benchmark.metrics import ivf_fold_topk_pct, ivf_gather_pct
from vearch_tpu.ops import perf_model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "gist1m-rabitq.b64x4-closed"
LO, HI, OFF = 0.0, 30_000_000.0, 5_000_000
MODULE = "jit_binary_refine_rerank(11425094880779214938)"
DISPATCH_NS = 9_000_000
Rec = namedtuple("Rec", ["service", "name", "trace_id", "span_id",
                         "parent_id", "t0_ns", "t1_ns", "cpu_ns", "tags"])

SCORE = ("%fusion.37 = f32[8,7816,8,128]{3,2,1,0:T(8,128)} fusion(u8[1000448,"
         "120]{0,1:T(8,128)(4,1)} %planes.1, u8[960]{0:T(1024)(128)(4,1)S(1)} "
         "%reshape.29, f32[1000448]{0:T(1024)} %row_vsq.1, f32[64,960]{1,0:"
         "T(8,128)} %queries.1), kind=kOutput, calls=%fused_computation.25")
BLOCK_MAX = ("%reduce_max.1 = f32[8,7816,8]{2,1,0:T(8,128)S(1)} reduce(f32[8,"
             "7816,8,128]{3,2,1,0:T(8,128)} %fusion.37, f32[]{:T(128)} "
             "%constant.126), dimensions={3}, to_apply=%region_1.3")
SORTS = [
    ("%sort.6 = (f32[64,7816]{1,0:T(8,128)}, s32[64,7816]{1,0:T(8,128)S(1)}) "
     "sort(f32[64,7816]{1,0:T(8,128)S(1)} %bitcast.71, s32[64,7816]{1,0:"
     "T(8,128)S(1)} %iota.11), dimensions={1}, is_stable=true, "
     "to_apply=%compare-greater-than.1", 331_000),
    ("%sort.10 = (f32[8192,512]{0,1:T(8,128)S(1)}, s32[8192,512]{0,1:T(8,128)"
     "S(1)}) sort(f32[8192,512]{0,1:T(8,128)S(1)} %copy.9, s32[8192,512]{0,1:"
     "T(8,128)S(1)} %iota.18.clone), dimensions={1}, "
     "to_apply=%compare-greater-than.0", 352_000),
    ("%sort.11 = (f32[64,65536]{1,0:T(8,128)S(1)}, s32[64,65536]{1,0:T(8,128)"
     "S(1)}) sort(f32[64,65536]{1,0:T(8,128)S(1)} %reshape.25, s32[64,65536]"
     "{1,0:T(8,128)S(1)} %reshape.27), dimensions={1}, "
     "to_apply=%compare-greater-than.0.clone", 2_366_000),
]
GATHERS = [
    ("%fusion = f32[32768,128]{1,0:T(8,128)S(1)} fusion(f32[8,7816,8,128]{3,2,"
     "1,0:T(8,128)} %fusion.37, s32[32768]{0:T(1024)S(1)} %bitcast.67), "
     "kind=kCustom, calls=%fused_computation", 377_000),
    ("%fusion.1 = s8[32768,1920]{1,0:T(8,128)(4,1)S(1)} fusion(s8[500224,1920]"
     "{1,0:T(8,128)(4,1)} %approx8.1, s32[32768]{0:T(1024)S(1)} "
     "%broadcast_clamp_fusion.1), kind=kCustom, calls=%fused_computation.1",
     927_000),
    ("%fusion.2 = f32[32768]{0:T(1024)S(1)} fusion(f32[1000448]{0:T(1024)S(1)}"
     " %copy-done, s32[32768]{0:T(1024)S(1)} %broadcast_clamp_fusion), "
     "kind=kCustom, calls=%fused_computation.2", 234_000),
    ("%fusion.3 = f32[32768]{0:T(1024)S(1)} fusion(f32[1000448]{0:T(1024)S(1)}"
     " %custom-call.17, s32[32768]{0:T(1024)S(1)} %broadcast_clamp_fusion), "
     "kind=kCustom, calls=%fused_computation.3", 234_000),
    ("%fusion.6 = s32[16384]{0:T(1024)S(1)} fusion(s32[64,512]{1,0:T(8,128)"
     "S(1)} %get-tuple-element.41, s32[16384]{0:T(1024)S(1)} %bitcast.68), "
     "kind=kCustom, calls=%fused_computation.6", 166_000),
    ("%fusion.4 = f32[16384,1920]{1,0:T(8,128)} fusion(f32[500000,1920]{1,0:"
     "T(8,128)} %base.1, s32[16384]{0:T(1024)S(1)} %broadcast_clamp_fusion.3)"
     ", kind=kCustom, calls=%fused_computation.4", 613_000),
    ("%fusion.5 = f32[16384]{0:T(1024)S(1)} fusion(f32[1000000]{0:T(1024)S(1)}"
     " %custom-call.18, s32[16384]{0:T(1024)S(1)} %broadcast_clamp_fusion.2),"
     " kind=kCustom, calls=%fused_computation.5", 116_000),
]
PRODUCTS = [
    ("%multiply_reduce_fusion = f32[64,512]{1,0:T(8,128)S(1)} fusion(f32[64,"
     "960]{1,0:T(8,128)S(1)} %copy-done.5, pred[64,512]{1,0:T(8,128)(4,1)S(1)}"
     " %fusion.44, s8[64,512,1920]{2,1,0:T(8,128)(4,1)S(1)} %bitcast.69), "
     "kind=kLoop, calls=%fused_computation.26", 107_000),
    # bare-named like a gather's fusion, but a loop fusion: the exact
    # product with the super-row's sub-row chosen inside it
    ("%fusion.41 = f32[64,256]{1,0:T(8,128)S(1)} fusion(f32[64,256,1920]{2,1,"
     "0:T(8,128)} %bitcast.10, f32[64,960]{1,0:T(8,128)S(1)} %copy-done.4, "
     "pred[64,256]{1,0:T(8,128)(4,1)S(1)} %copy-done.7), kind=kLoop, "
     "calls=%fused_computation.29", 181_000),
]
GATHER_NS = sum(d for _, d in GATHERS)   # 2,667,000
SELECT_NS = sum(d for _, d in SORTS)     # 3,049,000


def dispatch(start):
    ops, t = [], start + 1_000
    for name, dur in ([(SCORE, 2_543_000), (BLOCK_MAX, 339_000)] + SORTS
                      + GATHERS + PRODUCTS):
        ops.append([name, t, dur])
        t += dur + 1_000
    assert t <= start + DISPATCH_NS
    return [MODULE, start, DISPATCH_NS], ops


def one_plane():
    mods, ops = [], []
    for start in (1_000_000, 15_000_000):
        m, o = dispatch(start)
        mods.append(m)
        ops += o
    # the mask's pad of the next request: another module, its own gather
    mods.append(["jit__pad(7877342080849227743)", 11_000_000, 8_000])
    ops.append(["%fusion.3 = pred[1048576]{0} fusion(pred[1000000]{0} %p), "
                "kind=kCustom, calls=%fused_computation", 11_001_000, 6_000])
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": trace.MODULES_LINE, "events": mods},
            {"name": trace.OPS_LINE, "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [[trace.MARK, LO, HI - LO]]}]}]}


def one_request(tid, at, place_ns, launch_us=1_200):
    """A 64-row request: router at trace time `at`, engine 5,000 ns on,
    `refine.place` up to the launch (None: a program from before the
    span), device_get back 9,300,000 ns after."""
    t = at + OFF
    k0 = t + 5_500 + (place_ns or 700_000)

    def rec(service, name, sid, parent, t0, t1, tags=None):
        return Rec(service, name, tid * 32, f"{sid}{tid}", parent and
                   f"{parent}{tid}", t0, t1, None, tags or {})

    out = [
        rec("router", "rpc.serve", "rs", None, t, k0 + 9_350_000),
        rec("router", "router.search", "rq", "rs", t + 1_000, k0 + 9_340_000),
        rec("router", "router.scatter", "sc", "rq", t + 2_000,
            k0 + 9_330_000),
        rec("ps", "rpc.serve", "ps", "sc", t + 3_000, k0 + 9_320_000),
        rec("ps", "ps.search", "pq", "ps", t + 4_000, k0 + 9_310_000),
        rec("ps", "engine.search.emb", "es", "pq", t + 5_000, k0 + 9_305_000),
        rec("ps", "kernel.binary_refine_rerank", "ke", "pq", k0,
            k0 + 9_300_000,
            {"rows": 64, "bucket_rows": 64, "launch_us": launch_us}),
    ]
    if place_ns is not None:
        out.append(rec("ps", "refine.place", "rp", "pq", t + 5_500, k0, {
            "r0": 512, "r1": 256, "rows": 1_000_000,
            "plane_bytes": 128_057_344, "mirror_bytes": 968_433_664}))
    return out


class Obs:
    def __init__(self, tr):
        self.trace = tr
        self.trace_lo_ns, self.trace_hi_ns, self.trace_offset_ns = LO, HI, OFF
        self.t0, self.seconds = OFF / 1e9, (HI - LO) / 1e9
        self.config = cells.Cell(CELL).config
        self.peak = cells.peaks("TPU v5 lite")
        self.rows = 1_000_000
        self.rec = {"t_done": (np.array([11e6, 25e6]) + OFF) / 1e9,
                    "ok": np.array([True, True]),
                    "q_idx": np.zeros((2, 64), np.int64)}


@pytest.fixture()
def obs(monkeypatch):
    requests = (one_request("a", 200_000, 600_000)
                + one_request("b", 14_100_000, 800_000))
    monkeypatch.setattr(spans, "snapshot", lambda: (requests, 0))
    return Obs(one_plane())


def read(name, o):
    return cells.metric_reader(name)(o)


# -- the cell and its files ------------------------------------------------


def test_the_cell_is_found_by_name_with_its_files_and_metrics():
    cell = cells.Cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["name"] == "b64x4-closed"
    assert cell.traffic["warm_rows"] == [64, 128, 256]
    assert cfg["name"] == "gist1m-960-ivfrabitq"
    # the one cut, written down with its measured reason: rows, never
    # under half the published corpus; no width changes
    assert cfg["reduced"] == ["rows"] and len(cfg["reduced_why"]) > 200
    assert cfg["deployment"]["published_rows"] == 1_000_000
    assert (cfg["rows"], cfg["dimension"], cfg["metric"]) == (
        500_000, 960, "L2")
    (index,) = [f["index"] for f in cfg["space"]["fields"] if f.get("index")]
    assert index["index_type"] == "IVFRABITQ"
    assert index["params"] == {"ncentroids": 4096}  # `stage0` at its default
    assert cfg["space"]["partition_num"] == cfg["space"]["replica_num"] == 1
    assert cfg["search"] == {"k": 10, "index_params": {"rerank": 256}}
    assert cfg["serving"]["dispatch_tag"] == cfg["serving"]["program"] == \
        cfg["serving"]["kernel"] == "binary_refine_rerank"
    assert cfg["limits"] == {"recall_at_10_min": 0.95, "score_err_max": 3e-06}
    assert cfg["ps_config"] == {"quality": {"sample_rate": 0.0}}
    assert cfg["rehearsal"] == {"rows": 20000, "ncentroids": 64}
    assert cells.kernel(cfg["serving"]["kernel"]) is kern
    (entry,) = [c for c in cells.benchmark_json()["configs"]
                if c["name"] == cfg["name"]]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["rows"] and "\n" not in entry["source"]
    assert {m["name"] for m in cell.end_to_end} == {
        "search_qps", "search_p50_ms", "search_p95_ms", "recall_at_10",
        "setup_s"}
    mine = {"refine_place_ms", "refine_gather_pct", "refine_select_pct"}
    names = {m["name"] for m in cell.per_layer}
    assert mine <= names and "int8_scan_rerank_roofline" in names
    assert {"dispatch_launch_ms", "dispatch_wait_ms"} <= names
    assert not names & {"mesh_place_ms", "engine_filter_ms",
                        "ivf_gather_pct", "ivf_bucket_fill_pct"}
    for m in cells.benchmark_json()["per_layer"]:
        if m["name"] in mine:
            assert m["workloads"] == [CELL] and m["moves"] == "search_p50_ms"


def test_the_stated_r0_is_the_products_default():
    """`serving.r0` is not sent with a request: it states what
    `perf_model.refine_depths` gives at the engine's fetch-k tier for
    k = 10, and the kernel file reads it."""
    cfg = cells.Cell(CELL).config
    fetch_k = perf_model.bucket_fetch_k(cfg["search"]["k"])
    r0, _ = perf_model.refine_depths(fetch_k, cfg["rows"])
    assert "r0" not in cfg["search"]["index_params"]
    assert max(r0, cfg["search"]["index_params"]["rerank"]) == \
        cfg["serving"]["r0"] == kern.R0 == 512
    assert 512 in perf_model.FETCH_K_TIERS or 1024 in perf_model.FETCH_K_TIERS


# -- the kernel file --------------------------------------------------------


def test_needed_work_of_the_funnel_by_hand():
    assert kern.MODULE_SUBSTRING in MODULE
    # 2 query rows, 8,000 stored rows of 64 dims, r1 = 16 (r0 stays the
    # configuration's 512)
    w = kern.needed(rows=2, n=8_000, d=64, r=16)
    assert w["flops"] == 2 * (2 * 8_000 * 64 + 2 * 512 * 64 + 2 * 16 * 64)
    once = 8_000 * 64 // 8 + 2 * 4 * 8_000 + 8_000
    assert kern.needed(0, 8_000, 64, 16) == {"flops": 0.0,
                                             "bytes": float(once)}
    per_row = 64 * 4 + 512 * (64 + 8) + 16 * (64 * 4 + 4) + 16 * 8
    assert w["bytes"] == once + 2 * per_row
    # an r1 past the stated r0 takes r0 along: r0 >= r1 always
    assert kern.needed(1, 8_000, 64, 1024)["flops"] == \
        2 * 64 * (8_000 + 1024 + 1024)


def test_the_cells_dispatch_is_bound_by_compute_and_cannot_pass_100():
    v5e = cells.peaks("TPU v5 lite")
    work = kern.needed(64, 1_000_000, 960, 256)
    assert work["flops"] == 64 * 2 * 960 * (1_000_000 + 512 + 256)
    assert work["flops"] == pytest.approx(122.97e9, rel=1e-4)
    # per dispatch: 120 MB of planes + 8 MB of columns + 1 MB of mask
    assert kern.needed(0, 1_000_000, 960, 256)["bytes"] == 129_000_000
    t, bound = kern.least_seconds(work, v5e)
    assert bound == "compute" and t == pytest.approx(0.6242e-3, rel=1e-3)
    assert work["bytes"] / v5e["hbm_bytes_per_s"] == pytest.approx(
        0.2736e-3, rel=1e-3)
    # the traced dispatch of PR 34 at 1M rows took 9.08 ms: 6.9 %
    assert 100 * t / 9.08e-3 == pytest.approx(6.87, abs=0.05)
    # at the cell's 500,000 rows: half the operations, compute-bound
    half = kern.needed(64, 500_000, 960, 256)
    assert half["flops"] == 64 * 2 * 960 * (500_000 + 512 + 256)
    assert kern.least_seconds(half, v5e)[1] == "compute"
    # NOT needed: the unpacked +-1 operand, 16x the planes
    assert 1_000_000 * 960 * 2 == 16 * (1_000_000 * 960 // 8)
    assert work["bytes"] < 1_000_000 * 960 * 2 / 4


def test_roofline_reader_takes_this_kernel_file_by_the_configurations_name(
        obs):
    # 2 dispatches of 9 ms, 128 rows answered in the window
    row = kern.needed(1, 1_000_000, 960, 256)
    least = 128 * row["flops"] / 197e12
    got = read("int8_scan_rerank_roofline", obs)
    assert got == pytest.approx(100 * least / (2 * DISPATCH_NS * 1e-9))
    assert 0 < got < 100
    assert read("sched_rows_per_dispatch", obs) == pytest.approx(64.0)


# -- the three readers --------------------------------------------------------


def test_gather_and_select_shares_by_hand(obs):
    assert read("refine_gather_pct", obs) == pytest.approx(
        100 * GATHER_NS / DISPATCH_NS)
    assert read("refine_select_pct", obs) == pytest.approx(
        100 * SELECT_NS / DISPATCH_NS)
    g, s = ivf_gather_pct.is_gather, ivf_fold_topk_pct.is_selection
    assert all(g(name) for name, _ in GATHERS)
    assert all(s(name) for name, _ in SORTS)
    # the score fusion (an output fusion around the product, the unpack
    # inside it), the block maxima and the two products are the rest
    for name in [SCORE, BLOCK_MAX] + [n for n, _ in PRODUCTS]:
        assert not g(name) and not s(name), name
    assert (read("refine_gather_pct", obs) + read("refine_select_pct", obs)
            ) < 100 * (DISPATCH_NS - 2_543_000 - 339_000) / DISPATCH_NS


def test_place_is_the_spans_mean_and_the_dispatch_splits_at_the_launch(
        obs, monkeypatch):
    assert read("refine_place_ms", obs) == pytest.approx((0.6 + 0.8) / 2)
    assert read("dispatch_launch_ms", obs) == pytest.approx(1.2)
    assert read("dispatch_wait_ms", obs) == pytest.approx(9.3 - 1.2)
    # a program from before the span (the parent commit): nothing read
    monkeypatch.setattr(spans, "snapshot", lambda: (
        one_request("a", 200_000, None)
        + one_request("b", 14_100_000, None), 0))
    assert read("refine_place_ms", Obs(one_plane())) is None


@pytest.mark.parametrize("name", ["refine_place_ms", "refine_gather_pct",
                                  "refine_select_pct"])
def test_a_reader_with_nothing_to_read_returns_none(name, monkeypatch):
    """No trace (`--trace 0`), no span store, or a trace on which the
    serving program is not found by its module name: None, no raise."""
    o = Obs(one_plane())
    o.trace = None
    assert read(name, o) is None
    monkeypatch.setattr(spans, "snapshot", lambda: None)
    tr = one_plane()
    tr["planes"][0]["lines"][0]["events"] = [
        ["jit_int8_scan_rerank(1)", 1_000_000, DISPATCH_NS]]
    assert read(name, Obs(tr)) is None


# -- the rehearsal --------------------------------------------------------


def test_rehearsal_of_the_cell_serves_the_three_stage_program(tmp_path):
    """`--rehearse-cpu` of the new cell: the whole run at 20,000 x 960
    on the CPU backend, exit code 4, correct, served by
    `binary_refine_rerank` (the warm-up refuses any other tag), the new
    span's reader among the metrics reported."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax")}
    env.pop("XLA_FLAGS", None)  # one device: the cell's one chip
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2987654329", "--seconds", "3",
         "--trace", "1", "--rehearse-cpu"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 4, proc.stderr[-3000:]
    assert proc.stdout.strip() == ""  # a rehearsal prints no result line
    last = json.loads([ln for ln in proc.stderr.splitlines()
                       if ln.startswith('{"rehearsal"')][-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["checks"]["window_compiles"]["value"] == 0
    assert last["checks"]["write_read_delete_failed"]["value"] == 0
    assert {"refine_place_ms", "dispatch_launch_ms",
            "dispatch_wait_ms"} <= set(last["metrics_reported"])
    spans_line = json.loads([ln for ln in proc.stderr.splitlines()
                             if ln.startswith('{"msg": "spans"')][-1])
    assert "ps/kernel.binary_refine_rerank" in spans_line["self_ms"]
    assert "ps/refine.place" in spans_line["self_ms"]
