"""What PR 32 added to the benchmark for `sift1m-ivfflat.b64x4-closed`:
the kernel file's needed work by hand, the three readers on a hand-made
one-chip trace whose operation names are the chip's own (copied from
PR 32's traced run, shapes shortened), the cell found by name, and the
five older cells' generator specs still the parent's, byte for byte.

The trace: a 1 ms window, two 64-row dispatches of
`jit_ivfflat_candidates` of 300,000 ns each. A dispatch is the coarse
selection's sort (10,000) and a `while` of two scan steps (the chip's:
128), each step: a scalar `dynamic-slice` 1,000, the list gather
`%fusion.24` 20,000, the product 10,000, the mask's slot-by-slot lookup
`%fusion.26` 70,000, the fold's sort 15,000 and its `take_along_axis`
`%fusion.28` 14,000, a loop fusion that only NAMES a gather 5,000.
"""

import json
from collections import namedtuple

import numpy as np
import pytest

from benchmark import cells, run, spans, trace
from benchmark.kernels import ivfflat_scan as kern
from benchmark.metrics import ivf_fold_topk_pct, ivf_gather_pct

CELL = "sift1m-ivfflat.b64x4-closed"
LO, HI, OFF = 0.0, 1_000_000.0, 5_000_000
MODULE = "jit_ivfflat_candidates(10682588181293790708)"
Rec = namedtuple("Rec", ["service", "name", "trace_id", "span_id",
                         "parent_id", "t0_ns", "t1_ns", "cpu_ns", "tags"])

WHILE = ("%while.3 = (s32[]{:T(128)}, f32[64,256]{1,0:T(8,128)S(1)}, "
         "f32[4096,2048,128]{2,1,0:T(8,128)}) while((s32[]{:T(128)}, "
         "f32[64,256]{1,0:T(8,128)S(1)}, f32[4096,2048,128]{2,1,0:T(8,128)}) "
         "%tuple.36), condition=%region_4.11, body=%region_2.10")
COARSE_SORT = ("%sort.1 = (f32[64,1024]{1,0:T(8,128)}, s32[64,1024]{1,0:"
               "T(8,128)S(1)}) sort(f32[64,1024]{1,0:T(8,128)S(1)} %fusion.12,"
               " s32[64,1024]{1,0:T(8,128)S(1)} %iota.2), dimensions={1}, "
               "is_stable=true, to_apply=%compare-greater-than.0")
STEP = [
    ("%dynamic_slice.21 = s32[1]{0:T(128)} dynamic-slice(s32[128]{0:T(128)"
     "S(1)} %get-tuple-element.315, s32[]{:T(128)} %get-tuple-element.294), "
     "dynamic_slice_sizes={1}", 1_000),
    ("%fusion.24 = f32[64,2048,128]{2,1,0:T(8,128)S(1)} fusion(f32[4096,2048,"
     "128]{2,1,0:T(8,128)} %get-tuple-element.317, s32[1024]{0:T(1024)S(1)} "
     "%pad_clamp_fusion.2), kind=kCustom, calls=%fused_computation.2.clone",
     20_000),
    ("%multiply_reduce_fusion.4 = f32[64,2048]{1,0:T(8,128)S(1)} fusion(f32["
     "64,2048,128]{2,1,0:T(8,128)S(1)} %fusion.24, f32[64,128]{1,0:T(8,128)"
     "S(1)} %get-tuple-element.320), kind=kLoop, calls=%fused_computation.5",
     10_000),
    ("%fusion.26 = pred[131072]{0:T(1024)(128)(4,1)S(1)} fusion(pred[1000000]"
     "{0:T(1024)(128)(4,1)S(1)} %get-tuple-element.322, s32[131072]{0:T(1024)"
     "S(1)} %broadcast_clamp_fusion.2), kind=kCustom, calls="
     "%fused_computation.1.clone", 70_000),
    ("%sort.8 = (f32[64,2304]{1,0:T(8,128)S(1)}, s32[64,2304]{1,0:T(8,128)"
     "S(1)}) sort(f32[64,2304]{1,0:T(8,128)S(1)} %pad_maximum_fusion.2, s32["
     "64,2304]{1,0:T(8,128)S(1)} %iota.14), dimensions={1}, is_stable=true, "
     "to_apply=%compare-greater-than.1", 15_000),
    ("%fusion.28 = s32[16384]{0:T(1024)S(1)} fusion(s32[64,2304]{1,0:T(8,128)"
     "S(1)} %pad_add_fusion.2, s32[16384]{0:T(1024)S(1)} %bitcast.35), "
     "kind=kCustom, calls=%fused_computation.4.clone", 14_000),
    # names the ids' gather as an operand and is a loop fusion
    ("%pad_add_fusion.2 = s32[64,2304]{1,0:T(8,128)S(1)} fusion(s32[64,2048]"
     "{1,0:T(8,128)S(1)} %fusion.25, s32[64,256]{1,0:T(8,128)S(1)} "
     "%broadcast_select_fusion.2), kind=kLoop, calls=%fused_computation.7",
     5_000),
]


def dispatch(start):
    """(module event, its operations): the coarse sort, then the loop
    and, inside its span, two steps back to back."""
    ops = [[COARSE_SORT, start + 5_000, 10_000],
           [WHILE, start + 20_000, 275_000]]
    t = start + 21_000
    for _ in range(2):
        for name, dur in STEP:
            ops.append([name, t, dur])
            t += dur
    assert t <= start + 295_000
    return [MODULE, start, 300_000], ops


def one_plane():
    mods, ops = [], []
    for start in (100_000, 500_000):
        m, o = dispatch(start)
        mods.append(m)
        ops += o
    # the mask's pad of the next request: another module, its own gather
    mods.append(["jit__pad(7877342080849227743)", 420_000, 8_000])
    ops.append(["%fusion.3 = pred[1048576]{0} fusion(pred[1000000]{0} %p), "
                "kind=kCustom, calls=%fused_computation", 421_000, 6_000])
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": trace.MODULES_LINE, "events": mods},
            {"name": trace.OPS_LINE, "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [[trace.MARK, LO, HI - LO]]}]}]}


def one_request(tid, at, fill, probe_ns=1_500, launch_us=2):
    """A 64-row request: router at trace time `at`, engine 5,000 ns on,
    `ivf.probe` up to the launch, device_get back 310,000 ns after."""
    t = at + OFF
    k0 = t + 5_500 + probe_ns

    def rec(service, name, sid, parent, t0, t1, tags=None):
        return Rec(service, name, tid * 32, f"{sid}{tid}", parent and
                   f"{parent}{tid}", t0, t1, None, tags or {})

    out = [
        rec("router", "rpc.serve", "rs", None, t, k0 + 315_000),
        rec("router", "router.search", "rq", "rs", t + 1_000, k0 + 314_000),
        rec("router", "router.scatter", "sc", "rq", t + 2_000, k0 + 313_000),
        rec("ps", "rpc.serve", "ps", "sc", t + 3_000, k0 + 312_000),
        rec("ps", "ps.search", "pq", "ps", t + 4_000, k0 + 311_000),
        rec("ps", "engine.search.emb", "es", "pq", t + 5_000, k0 + 310_500),
        rec("ps", "kernel.ivfflat_scan", "ke", "pq", k0, k0 + 310_000,
            {"rows": 64, "bucket_rows": 64, "launch_us": launch_us}),
    ]
    if fill is not None:
        out.append(rec("ps", "ivf.probe", "ip", "pq", t + 5_500, k0,
                       {"nprobe": 32, "cap": 8192, "fill": fill}))
    return out


class Obs:
    def __init__(self, tr):
        self.trace = tr
        self.trace_lo_ns, self.trace_hi_ns, self.trace_offset_ns = LO, HI, OFF
        self.t0, self.seconds = OFF / 1e9, (HI - LO) / 1e9
        self.config = cells.Cell(CELL).config
        self.peak = cells.peaks("TPU v5 lite")
        self.rows = 1_000_000
        self.rec = {"t_done": (np.array([420_000.0, 820_000.0]) + OFF) / 1e9,
                    "ok": np.array([True, True]),
                    "q_idx": np.zeros((2, 64), np.int64)}


@pytest.fixture()
def obs(monkeypatch):
    requests = (one_request("a", 90_000, 0.119209)
                + one_request("b", 490_000, 0.25))
    monkeypatch.setattr(spans, "snapshot", lambda: (requests, 0))
    return Obs(one_plane())


def read(name, o):
    return cells.metric_reader(name)(o)


# -- the cell and its files ------------------------------------------------


def test_the_cell_is_found_by_name_with_its_files_and_metrics():
    cell = cells.Cell(CELL)
    cfg = cell.config
    assert cell.chips == 1 and cell.traffic["name"] == "b64x4-closed"
    assert cfg["name"] == "sift1m-ivfflat" and cfg["reduced"] == []
    assert (cfg["rows"], cfg["dimension"], cfg["metric"]) == (
        1_000_000, 128, "L2")
    (index,) = [f["index"] for f in cfg["space"]["fields"] if f.get("index")]
    assert index["index_type"] == "IVFFLAT"
    assert index["params"] == {"ncentroids": 1024}
    assert cfg["search"] == {"k": 10, "index_params": {"nprobe": 32,
                                                       "rerank": 256}}
    assert cfg["serving"]["dispatch_tag"] == "ivfflat_scan"
    assert cfg["limits"] == {"recall_at_10_min": 0.95, "score_err_max": 3e-06}
    assert cells.kernel(cfg["serving"]["kernel"]) is kern
    assert {m["name"] for m in cell.end_to_end} == {
        "search_qps", "search_p50_ms", "search_p95_ms", "recall_at_10",
        "setup_s"}
    mine = {"ivf_bucket_fill_pct", "ivf_fold_topk_pct", "ivf_gather_pct"}
    names = {m["name"] for m in cell.per_layer}
    assert mine <= names and "int8_scan_rerank_roofline" in names
    assert not names & {"mesh_place_ms", "engine_filter_ms"}
    for m in cells.benchmark_json()["per_layer"]:
        if m["name"] in mine:
            assert m["workloads"] == [CELL] and m["moves"] == "search_p50_ms"


def test_every_per_layer_reader_of_the_cell_reads_a_number_or_none(obs):
    """Every metric without a `workloads` key that moves search_p50_ms
    reports here unasked: on the hand-made run (two requests, their
    reply's profile columns, the window's records) each of the cell's
    readers reads a number or None, none raises."""
    from benchmark import loadgen

    done = obs.rec["t_done"]
    obs.win = {"t_send": done - 0.320, "t_done": done,
               # rpc 315, merge 0.1, engine total 311, queue 0, gate 0,
               # dispatches 310 ms in one launch
               "prof": np.tile([315.0, 0.1, 311.0, 0.0, 0.0, 310.0, 1.0],
                               (2, 1))}
    obs.prof = lambda field: obs.win["prof"][
        :, loadgen.PROFILE_FIELDS.index(field)]
    obs.lat_ms = np.array([320.0, 320.0])
    obs.memory_peak_bytes = 4_700_000_000
    got = {m["name"]: cells.metric_reader(m["name"])(obs)
           for m in cells.Cell(CELL).per_layer}
    assert all(v is None or np.isfinite(v) for v in got.values()), got
    assert got["router_self_ms"] == pytest.approx(5.0)
    assert got["ps_self_ms"] == pytest.approx(4.0)
    assert got["engine_host_ms"] == pytest.approx(1.0)
    assert got["dispatch_ms"] == pytest.approx(310.0)
    assert got["hbm_peak_gb"] == pytest.approx(4.7)
    assert got["device_idle_pct"] == pytest.approx(
        100 * (1 - (2 * (10_000 + 275_000) + 6_000) / 1_000_000))
    assert sum(got[f"idle_{layer}_pct"] for layer in spans.LAYERS
               ) == pytest.approx(got["device_idle_pct"])


# -- the kernel file --------------------------------------------------------


def test_needed_work_of_the_probe_scan_by_hand():
    assert (kern.NLIST, kern.NPROBE) == (1024, 32)  # the configuration's
    assert kern.MODULE_SUBSTRING in MODULE
    # 2 query rows, 102,400 stored rows of 8 dims, 4 results: a query
    # probes 32 lists of 100 rows in the mean
    w = kern.needed(rows=2, n=102_400, d=8, r=4)
    assert w["flops"] == 2 * (2 * 1024 * 8 + 2 * 3200 * 8)  # 135,168
    centroids, one_pass = 1024 * 8 * 4, 3200 * (8 * 4 + 4 + 4)
    assert kern.needed(0, 102_400, 8, 4) == {
        "flops": 0.0, "bytes": float(centroids + one_pass)}
    assert w["bytes"] == centroids + one_pass + 2 * (8 * 4 + 4 * 8)
    # ONE pass over nprobe lists a dispatch, not one a query: the
    # program's own 64 gathers a step are not needed work
    assert (kern.needed(64, 102_400, 8, 4)["bytes"]
            - kern.needed(1, 102_400, 8, 4)["bytes"]) == 63 * 64


def test_the_cells_dispatch_is_bound_by_memory_and_far_from_it():
    v5e = cells.peaks("TPU v5 lite")
    work = kern.needed(64, 1_000_000, 128, 256)
    t, bound = kern.least_seconds(work, v5e)
    # 524,288 centroid bytes + 31,250 rows x 520 + 64 x (512 + 2048)
    assert work["bytes"] == 524_288 + 16_250_000 + 163_840
    assert work["flops"] == 64 * (2 * 1024 * 128 + 2 * 31_250 * 128)
    assert bound == "memory" and t == pytest.approx(16_938_128 / 819e9)
    # the traced dispatch of PR 32 took 187.4 ms: a hundredth of a percent
    assert 100 * t / 187.4e-3 == pytest.approx(0.011, abs=0.001)
    # at ONE row a dispatch the floor is still one query's own lists
    t1, _ = kern.least_seconds(kern.needed(1, 1_000_000, 128, 256), v5e)
    assert t1 == pytest.approx((524_288 + 16_250_000 + 2_560) / 819e9)


def test_roofline_reader_takes_this_kernel_file_by_the_configurations_name(
        obs):
    # 2 dispatches of 300,000 ns, 128 rows answered in the window
    none = kern.needed(0, 1_000_000, 128, 256)
    row = kern.needed(1, 1_000_000, 128, 256)
    nbytes = 2 * none["bytes"] + 128 * (row["bytes"] - none["bytes"])
    flops = 128 * row["flops"]
    least = max(nbytes / 819e9, flops / 197e12)
    assert least == nbytes / 819e9
    got = read("int8_scan_rerank_roofline", obs)
    assert got == pytest.approx(100 * least / 600_000e-9)
    assert 0 < got < 100
    assert read("sched_rows_per_dispatch", obs) == pytest.approx(64.0)


# -- the three readers --------------------------------------------------------


def test_gather_share_by_hand(obs):
    # a step's dynamic-slice 1,000 + %fusion.24 20,000 + %fusion.26
    # 70,000 + %fusion.28 14,000, two steps, of a 300,000 ns dispatch;
    # the `while` that spans them, the loop fusion that names %fusion.25
    # and the other module's %fusion.3 do not count
    assert read("ivf_gather_pct", obs) == pytest.approx(
        100 * 2 * 105_000 / 300_000)
    g = ivf_gather_pct
    assert g.is_gather(STEP[1][0]) and g.is_gather(STEP[3][0])
    assert g.is_gather("%gather.59 = f32[64,10368]{1,0} gather(f32[8] %p)")
    assert g.is_gather("%dynamic-slice.2 = f32[8]{0} dynamic-slice(%p)")
    assert not g.is_gather(STEP[2][0]) and not g.is_gather(STEP[6][0])
    assert not g.is_gather(  # a descriptive name is a loop fusion's
        "%pad_clamp_fusion.2 = s32[1024]{0} fusion(%f), kind=kCustom")
    assert not g.is_gather("%fusion.9 = f32[8]{0} fusion(%p), kind=kLoop")
    assert g.opcode(WHILE) == "while" and g.opcode(STEP[4][0]) == "sort"
    assert g.opcode(STEP[1][0]) == "fusion" and g.opcode(MODULE) == ""


def test_fold_share_by_hand(obs):
    # the coarse sort 10,000 and two folds' sorts of 15,000
    assert read("ivf_fold_topk_pct", obs) == pytest.approx(
        100 * 40_000 / 300_000)
    f = ivf_fold_topk_pct
    assert f.is_selection(COARSE_SORT) and f.is_selection(STEP[4][0])
    assert f.is_selection('%custom-call = (f32[64,256]{1,0}, s32[64,256]'
                          '{1,0}) custom-call(%x), custom_call_target="TopK"')
    assert f.is_selection("%top_k.3 = f32[8]{0} topk(%x)")
    assert not f.is_selection(STEP[5][0])  # the fold's gather
    assert not f.is_selection(  # names a sort as an operand
        "%slice.42 = f32[64,256]{1,0} slice(f32[64,2304]{1,0} %sort.8)")


def test_shares_leave_room_for_the_product(obs):
    """Gathers, sorts and what neither counts (the product, the loop
    fusions, the gaps between operations) are all of a dispatch."""
    rest = 2 * (10_000 + 5_000)
    assert (read("ivf_gather_pct", obs) + read("ivf_fold_topk_pct", obs)
            ) == pytest.approx(100 * (300_000 - rest - 20_000) / 300_000)


def test_bucket_fill_is_the_probe_phases_tag(obs, monkeypatch):
    assert read("ivf_bucket_fill_pct", obs) == pytest.approx(
        100 * (0.119209 + 0.25) / 2)
    assert read("dispatch_launch_ms", obs) == pytest.approx(2e-3)
    assert read("dispatch_wait_ms", obs) == pytest.approx(310e-3 - 2e-3)
    # a program from before the span (the parent commit): nothing read
    monkeypatch.setattr(spans, "snapshot", lambda: (
        one_request("a", 90_000, None) + one_request("b", 490_000, None), 0))
    assert read("ivf_bucket_fill_pct", Obs(one_plane())) is None


@pytest.mark.parametrize("name", ["ivf_bucket_fill_pct", "ivf_fold_topk_pct",
                                  "ivf_gather_pct"])
def test_a_reader_with_nothing_to_read_returns_none(name, monkeypatch):
    """No trace (`--trace 0`), no span store, or a trace on which the
    serving program is not found by its module name: None, no raise."""
    o = Obs(one_plane())
    o.trace = None
    assert read(name, o) is None
    monkeypatch.setattr(spans, "snapshot", lambda: None)
    tr = one_plane()
    tr["planes"][0]["lines"][0]["events"] = [
        ["jit_int8_scan_rerank(1)", 100_000, 300_000]]
    assert read(name, Obs(tr)) is None


# -- the cells that were there ------------------------------------------------


@pytest.mark.parametrize("cell", [
    "sift1m.b64x4-closed", "cohere1m.b64x4-closed", "sift1m.b1-open",
    "sift1m.b64x4-filter", "deep10m-mesh4.b64x4-closed"])
def test_an_older_cells_generator_specs_are_the_parents(cell):
    """Byte for byte what PR 31's `run.generator_specs` wrote for each
    worker: the new configuration and cell changed no other cell's
    traffic, configuration or search parameters."""
    from test_bh_run import parent_spec

    c = cells.Cell(cell)
    specs = run.generator_specs(c.traffic, c.config, "r:1", "/p/pool.npy",
                                "/p", 7, 10.0, 33.25, False)
    want = []
    for w in range(int(c.traffic["processes"])):
        spec = parent_spec(c.config, c.traffic, w)
        if c.traffic.get("filter"):
            spec["filter"] = {**c.traffic["filter"], "modulo": 50}
        want.append(spec)
    assert json.dumps(specs) == json.dumps(want)
    # and the new cell's differ from sift closed's in the index's own
    # parameters alone
    new = run.generator_specs(cells.Cell(CELL).traffic,
                              cells.Cell(CELL).config, "r:1", "/p/pool.npy",
                              "/p", 7, 10.0, 33.25, False)
    if cell == "sift1m.b64x4-closed":
        assert [{**s, "index_params": None} for s in new] == [
            {**s, "index_params": None} for s in specs]
        assert new[0]["index_params"] == {"nprobe": 32, "rerank": 256}
