"""The benchmark's readers on a four-plane device trace: the cell
`deep10m-mesh4.b64x4-closed` runs ONE program over the four chips of a
host, so its trace has a plane per chip and four module events a
dispatch. A hand-made trace (100 us window, two 64-row dispatches) and
the two requests that own them; every expected number is worked by hand
from `four_planes()` and `two_requests()` below.

Per chip a dispatch is 20,000 ns: score and selection (`%fusion.1`),
`%all-gather.3`, the merge's fusion (which NAMES the gathered array as
an operand and is no collective), `%all-reduce.5` (the pmax). Chip 3
arrives last at the gather and waits least; chip 1's second dispatch
runs 2,000 ns longer.
"""

from collections import namedtuple

import numpy as np
import pytest

from benchmark import cells, spans, trace
from benchmark.kernels import sharded_fused_scan_rerank as kern

CELL = "deep10m-mesh4.b64x4-closed"
LO, HI, OFF = 0.0, 100_000.0, 1_000_000  # window; span time = trace + OFF
MODULE = "jit_sharded_fused_scan_rerank(8812)"
Rec = namedtuple("Rec", ["service", "name", "trace_id", "span_id",
                         "parent_id", "t0_ns", "t1_ns", "cpu_ns", "tags"])


def dispatch(start, score=12_000, gather=3_000):
    """One chip's part of a dispatch: (module event, its operations)."""
    ops, t = [], start
    for name, dur in (
            ("%fusion.1 = f32[8,7816,8,128]{3,2,1,0:T(8,128)} fusion(...)",
             score),
            ("%all-gather.3 = f32[64,1024]{1,0:T(8,128)} all-gather(...)",
             gather),
            ("%fusion.7 = f32[64,256]{1,0} fusion(f32[64,1024] "
             "%all-gather.3)", 3_000),
            ("%all-reduce.5 = f32[64,256]{1,0} all-reduce(...)", 2_000)):
        ops.append([name, t, dur])
        t += dur
    return [MODULE, start, t - start], ops


def four_planes(extra_on_chip3=0):
    planes = []
    for chip in range(4):
        mods, ops = [], []
        for d, start in enumerate((10_000, 50_000)):
            m, o = dispatch(
                start,
                score=(14_000 if chip == 3 or (chip == 1 and d == 1)
                       else 12_000),
                gather=1_000 if chip == 3 else 3_000)
            mods.append(m)
            ops += o
        if chip == 3 and extra_on_chip3:
            ops.append(["%copy.9 = f32[8]{0} copy(...)", 80_000,
                        extra_on_chip3])
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": trace.MODULES_LINE, "events": mods},
            {"name": trace.OPS_LINE, "events": ops}]})
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [[trace.MARK, LO, HI - LO]]}]})
    return {"planes": planes}


def one_request(tid, at, place_ns, launch_us):
    """A 64-row request whose router span starts at trace time `at`;
    the engine is entered 5,000 ns later, the program launched after
    `place_ns` of mesh.place, its device_get back 22,000 ns after."""
    t = at + OFF
    k0 = t + 5_500 + place_ns

    def rec(service, name, sid, parent, t0, t1, tags=None):
        return Rec(service, name, tid * 32, f"{sid}{tid}", parent and
                   f"{parent}{tid}", t0, t1, None, tags or {})

    return [
        rec("router", "rpc.serve", "rs", None, t, k0 + 27_000),
        rec("router", "router.search", "rq", "rs", t + 1_000, k0 + 26_000),
        rec("router", "router.scatter", "sc", "rq", t + 2_000, k0 + 25_000),
        rec("ps", "rpc.serve", "ps", "sc", t + 3_000, k0 + 24_000),
        rec("ps", "ps.search", "pq", "ps", t + 4_000, k0 + 23_000),
        rec("ps", "engine.search.emb", "es", "pq", t + 5_000, k0 + 22_500),
        rec("ps", "mesh.place", "mp", "pq", t + 5_500, k0,
            {"bytes": 64 * 96 * 4}),
        rec("ps", "kernel.sharded_fused_scan_rerank", "ke", "pq", k0,
            k0 + 22_000, {"rows": 64, "bucket_rows": 64,
                          "launch_us": launch_us}),
    ]


def two_requests():
    # R1 reaches the router at 2,000 and launches at 9,000; R2 at
    # 42,000 and 49,500: each program starts inside its kernel window
    return (one_request("a", 2_000, 1_500, 2)
            + one_request("b", 42_000, 2_000, 3))


class Obs:
    def __init__(self, tr):
        self.trace = tr
        self.trace_lo_ns, self.trace_hi_ns, self.trace_offset_ns = LO, HI, OFF
        self.t0, self.seconds = OFF / 1e9, (HI - LO) / 1e9
        self.config = cells.Cell(CELL).config
        self.peak = cells.peaks("TPU v5 lite")
        self.rows = 4000  # stored rows: 1000 a shard
        # both requests were answered inside the traced window
        self.rec = {"t_done": (np.array([36_000.0, 77_000.0]) + OFF) / 1e9,
                    "ok": np.array([True, True]),
                    "q_idx": np.zeros((2, 64), np.int64)}


@pytest.fixture()
def obs(monkeypatch):
    monkeypatch.setattr(spans, "snapshot", lambda: (two_requests(), 0))
    return Obs(four_planes())


def read(name, o):
    return cells.metric_reader(name)(o)


def test_needed_work_of_the_mesh_kernel_by_hand():
    # 2 query rows, 1000 stored rows of 8 dims over 4 shards, 4 candidates
    w = kern.needed(rows=2, n=1000, d=8, r=4)
    assert w["flops"] == 2 * 2 * 1000 * 8 + 2 * 2 * 4 * 8  # the whole mesh's
    shard = 250 * 8 + 2 * 4 * 250                          # ONE shard's slice
    assert kern.needed(0, 1000, 8, 4) == {"flops": 0.0, "bytes": shard}
    assert w["bytes"] == shard + 2 * 4 * (8 * 4 + 4) + 2 * 8 * 4
    assert kern.SHARDS == 4
    assert kern.MODULE_SUBSTRING in MODULE
    assert "int8_scan_rerank" not in kern.MODULE_SUBSTRING
    # four events a dispatch read the mirror once: the one-chip kernel's
    one = cells.kernel("int8_scan_rerank").needed(0, 1000, 8, 4)["bytes"]
    assert kern.SHARDS * shard == one


def test_roofline_charges_a_shard_per_event_and_the_rows_once(obs):
    # 8 events (2 dispatches x 4 chips) of 20,000 ns, chip 1's second
    # 22,000; 128 rows; per row 2*4000*96 + 2*256*96 operations and
    # 256 raw rows of 388 bytes + a 384-byte query
    device_s = (7 * 20_000 + 22_000) / 1e9
    nbytes = 8 * 1000 * (96 + 8) + 128 * (256 * 388 + 384)
    flops = 128 * (2 * 4000 * 96 + 2 * 256 * 96)
    least = max(nbytes / 819e9, flops / 197e12)
    assert least == nbytes / 819e9  # bound by memory
    assert read("int8_scan_rerank_roofline", obs) == pytest.approx(
        100 * least / device_s)
    assert read("int8_scan_rerank_roofline", obs) < 100


def test_rows_per_dispatch_reads_a_quarter_on_four_planes(obs):
    """Harness item (b) of PERF.md section 7: the reader counts a
    dispatch once per chip's module event."""
    assert read("sched_rows_per_dispatch", obs) == pytest.approx(64 / 4)
    assert read("sched_bucket_fill_pct", obs) == pytest.approx(100.0)


def test_collective_share_by_hand(obs):
    # chips 0-2: (3,000 + 2,000) / 20,000; chip 1's second dispatch
    # 5,000 / 22,000; chip 3: (1,000 + 2,000) / 20,000. %fusion.7 names
    # %all-gather.3 as an operand and does not count
    chip1 = (5_000 + 5_000) / 42_000
    assert read("mesh_collective_pct", obs) == pytest.approx(
        100 * (0.25 + chip1 + 0.25 + 0.15) / 4)
    from benchmark.metrics.mesh_collective_pct import is_collective
    assert is_collective("%all-gather-start.2 = (f32[8]) all-gather-start()")
    assert is_collective("%collective-permute.1 = f32[8] collective-permute()")
    assert not is_collective("%fusion.7 = f32[8] fusion(%all-reduce.5)")


def test_chip_skew_by_hand(obs):
    # busy 40,000 on three chips and 42,000 on chip 1: mean 40,500
    assert read("mesh_chip_skew_pct", obs) == pytest.approx(
        100 * 2_000 / 40_500)
    assert read("device_idle_pct", obs) == pytest.approx(59.5)


def test_place_and_launch_by_hand(obs):
    assert read("mesh_place_ms", obs) == pytest.approx((1_500 + 2_000) / 2e6)
    assert read("dispatch_launch_ms", obs) == pytest.approx(2.5e-3)
    assert read("dispatch_wait_ms", obs) == pytest.approx(22e-3 - 2.5e-3)


def test_idle_gaps_of_the_first_chip_go_to_the_owning_request(obs):
    """Harness item (a): gaps are the FIRST chip's (60,000 ns idle), the
    total they are held to is the window minus the MEAN busy time
    (59,500): inside the reader's 0.5 % of the window here. The time in
    mesh.place is the engine's."""
    ms = spans.of(obs).idle_ms_by_layer()
    # [0, 10,000): R1 at the router from 2,000, hop at 4,000, PS from
    # 5,000, engine (engine.search, mesh.place, kernel.*) from 7,000;
    # [30,000, 50,000): R2 the same from 42,000; [70,000, 100,000): no
    # dispatch ends it
    assert ms == pytest.approx({
        "arrival": (2_000 + 12_000 + 30_000) / 1e6, "router": 4_000 / 1e6,
        "ps": 6_000 / 1e6, "sched": 0.0, "engine": 6_000 / 1e6})
    assert sum(read(f"idle_{k}_pct", obs) for k in spans.LAYERS) == \
        pytest.approx(60.0)


def test_idle_sum_raises_when_the_chips_busy_times_differ(monkeypatch):
    """What (a) would look like on the chip: 10,000 ns more work on chip
    3 moves the mean busy time 2,500 ns off the first chip's, 2.5 % of
    the window, and the accepted reader's own check fires."""
    monkeypatch.setattr(spans, "snapshot", lambda: (two_requests(), 0))
    skewed = Obs(four_planes(extra_on_chip3=10_000))
    with pytest.raises(AssertionError, match="idle shares"):
        spans.of(skewed).idle_ms_by_layer()


def test_one_plane_or_another_program_reads_nothing(monkeypatch):
    monkeypatch.setattr(spans, "snapshot", lambda: None)
    one = Obs({"planes": four_planes()["planes"][:1]})
    assert read("mesh_chip_skew_pct", one) is None
    assert read("mesh_place_ms", one) is None
    # the parent of the PR that named the program: module `jit_run`
    parent = four_planes()
    for p in parent["planes"][:4]:
        for e in p["lines"][0]["events"]:
            e[0] = "jit_run(77)"
    assert read("mesh_collective_pct", Obs(parent)) is None
    assert read("int8_scan_rerank_roofline", Obs(parent)) is None
    untraced = Obs(None)
    for name in ("mesh_collective_pct", "mesh_chip_skew_pct",
                 "mesh_place_ms"):
        assert read(name, untraced) is None
