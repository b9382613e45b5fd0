"""The readers of the program's spans (benchmark/spans.py and the
metrics that read it), on a small hand-made span set laid on the clock
of the recorded device trace (recorded_spans.json: span time = trace
time + 7 s). Four requests:

- R1 (a1..): one 64-row request whose dispatch is the trace's second scan
  program;
- R2, R3 (b2.., c3..): one row each, co-batched on the third scan program
  (one kernel window replayed under both; R2's router.search is earlier);
- R4 (d4..): on the sixth scan program, answered after the window closed;

and of the process two proc.gc spans (one inside the window) and a
ps.flush. Every expected number below is worked by hand from that file.
"""

import copy
import json
import os
from collections import namedtuple

import numpy as np
import pytest

from benchmark import cells, spans, trace

HERE = os.path.dirname(os.path.abspath(__file__))
LO, HI = 1e6, 61e6  # the recorded cut's window: its mark's span

with open(os.path.join(HERE, "recorded_spans.json")) as _f:
    RECORDED = json.load(_f)
Rec = namedtuple("Rec", RECORDED["fields"])
OFF = RECORDED["trace_offset_ns"]


def records(shift_ns=0, drop=()):
    return [Rec(*r)._replace(t0_ns=r[5] + shift_ns, t1_ns=r[6] + shift_ns)
            for r in copy.deepcopy(RECORDED["spans"]) if r[3] not in drop]


class Obs:
    """What run.py hands a reader, as far as these readers look."""

    def __init__(self, traced=True):
        with open(os.path.join(HERE, "recorded_trace.json")) as f:
            self.trace = json.load(f) if traced else None
        self.trace_lo_ns, self.trace_hi_ns, self.trace_offset_ns = LO, HI, OFF
        self.t0, self.seconds = RECORDED["t0_s"], RECORDED["seconds"]
        self.config = cells.Cell("sift1m.b1-open").config
        self.lat_ms = np.array([9.0, 11.0, 10.5, 30.0, 10.0])


@pytest.fixture()
def store(monkeypatch):
    """Put a span set where the program's snapshot() would be read."""
    def put(recs, evicted=0):
        monkeypatch.setattr(spans, "snapshot", lambda: (recs, evicted))
        return Obs()
    return put


def read(name, obs):
    return cells.metric_reader(name)(obs)


def test_self_time_counts_overlapping_children_once():
    assert spans.covered_ns(0, 100, [(10, 30), (20, 50), (90, 140)]) == 50
    assert spans.covered_ns(0, 100, []) == 0
    q = next(q for q in spans.Analysis(records(), Obs()).requests
             if q.trace_id.startswith("a1"))
    by_id = {s.span_id: s for s in q.spans}
    # ps.search 10,180 us; its children cover 580 (pre, gate, pre) + 50
    # (filter) + 9,370 (engine.search.v, with kernel.* inside it counted
    # once) + 80 (merge, shape) + 40 (post) = 10,120
    assert q.self_ns(by_id["pq1"]) == 60_000
    assert q.self_ns(by_id["es1"]) == 9_370_000  # a sibling is no child
    assert q.self_ns(by_id["rq1"]) == 23_000     # scatter and merge are out


def test_window_keeps_requests_answered_inside_it():
    a = spans.Analysis(records(), Obs())
    assert len(a.requests) == 4  # the process-level trace is no request
    assert sorted(q.trace_id[:2] for q in a.window) == ["a1", "b2", "c3"]


def test_each_reader_by_hand(store):
    obs = store(records())
    # router: R1 22+35+80+23+80 = 240 us, R2 140, R3 125
    assert read("router_span_self_ms", obs) == pytest.approx(0.505 / 3)
    # PS: R1 35+208+35+60+570+10+40 = 958 us, R2 480, R3 445
    assert read("ps_span_self_ms", obs) == pytest.approx(1.883 / 3)
    # wall - CPU over decode, pre, pre, post, encode: R1 58+180+10+5+5,
    # R2 10+90+5+80+1, R3 5+10+2+50+1
    assert read("ps_span_wait_ms.open", obs) == pytest.approx(0.512 / 3)
    # three dispatches started inside the trace, the co-batched one once
    assert read("sched_bucket_fill_pct", obs) == pytest.approx(
        100 * (64 + 2 + 64) / (64 + 8 + 64))
    assert read("dispatch_launch_ms", obs) == pytest.approx(0.7 / 3)
    assert read("dispatch_wait_ms", obs) == pytest.approx(
        (8.87 + 2 * 8.958) / 3)
    assert read("dispatch_launch_ms", obs) + read("dispatch_wait_ms", obs) \
        == pytest.approx((9.17 + 2 * 9.158) / 3)
    # one 1.2 ms collection in a 60 ms window; the other came after it
    assert read("host_pause_ms_per_s", obs) == pytest.approx(20.0)
    assert read("traced_search_p50_ms.open", obs) == pytest.approx(10.5)


def test_a_gap_is_cut_at_the_boundaries_of_the_request_that_ended_it():
    a = spans.Analysis(records(), Obs())
    q1, q2 = (next(q for q in a.requests if q.trace_id.startswith(t))
              for t in ("a1", "b2"))
    # the gap before the sampler's scan: R1 had reached the router;
    # rpc.decode 5,538, rpc.serve 2,000, router.search 3,000 (router);
    # the hop 2,000 and the PS's rpc.serve 1,949 (ps)
    assert dict(q1.cut(10179462 + OFF, 10193949 + OFF)) == {
        "router": 10538.0, "ps": 3949.0}
    # the gap before the third scan's pad: R2 sat in the scheduler's
    # queue until 20,450,000, then batch.pack 2,000 (sched), then
    # engine.filter 1,000 and engine.search.v 1,051 (engine)
    assert dict(q2.cut(20444199 + OFF, 20454051 + OFF)) == {
        "sched": 7801.0, "engine": 2051.0}
    assert q1.layer_at(10_100_000 + OFF) == "arrival"  # before its root


def test_idle_shares_add_up_to_the_device_idle_share(store):
    obs = store(records())
    shares = {k: read(f"idle_{k}_pct", obs) for k in spans.LAYERS}
    assert sum(shares.values()) == pytest.approx(
        read("device_idle_pct", obs), abs=1e-9)
    ms = spans.of(obs).idle_ms_by_layer()
    # only R2's wait in the queue is the scheduler's
    assert ms["sched"] == pytest.approx(0.007801)
    # the gap worked above, and 15 one- and two-nanosecond gaps inside
    # the programs that ran while R1 was in the router (and the PS)
    assert ms["router"] == pytest.approx(0.010538 + 15e-6)
    assert ms["ps"] == pytest.approx(0.003949 + 15e-6)
    # the 2.15 ms tail that no dispatch ends, the first 9 ms before R1
    # arrived, and the fourth and fifth scans, which no request owns and
    # whose next dispatch is R4's, not yet at the router
    assert ms["arrival"] == pytest.approx(2.231037)
    assert ms["arrival"] > 2.148552
    assert sum(ms.values()) == pytest.approx(2.294921)


def test_a_pad_program_inside_a_waiting_kernel_window_is_no_dispatch():
    """The third scan's pad starts 9 us after the second scan ends,
    inside R1's kernel window, which closes 15 us after it."""
    a = spans.Analysis(records(), Obs())
    mods = [("jit__pad(1)", 20453695.0, 20458002.0),
            ("jit_int8_scan_rerank(2)", 20466571.0, 29614003.0)]
    owners = a.module_owners(mods)
    assert owners[0] is None and owners[1].trace_id.startswith("b2")


SPAN_READERS = (
    "router_span_self_ms", "ps_span_self_ms", "ps_span_wait_ms",
    "sched_bucket_fill_pct", "dispatch_launch_ms", "dispatch_wait_ms",
    "idle_arrival_pct", "idle_router_pct", "idle_ps_pct", "idle_sched_pct",
    "idle_engine_pct", "host_pause_ms_per_s")


def test_nothing_to_read_reads_as_none(store, monkeypatch):
    declared = {m["name"] for m in cells.benchmark_json()["per_layer"]}
    names = [n + v for n in SPAN_READERS for v in ("", ".open")]
    assert set(names) <= declared
    empty = store([])
    assert spans.of(empty) is None  # read, and kept, before the next patch
    untraced = Obs(traced=False)
    monkeypatch.setattr(spans, "snapshot", lambda: None)  # the parent
    no_store = Obs()
    for name in names:
        assert read(name, empty) is None, name
        assert read(name, untraced) is None, name
        assert read(name, no_store) is None, name
    assert read("traced_search_p50_ms", untraced) is None


def test_a_span_set_on_the_wrong_offset_changes_the_reading(store):
    """Shifted by 5 ms, the kernel windows no longer hold their
    programs' starts: other gaps get other owners."""
    good = spans.of(store(records())).idle_ms_by_layer()
    bad = spans.of(store(records(shift_ns=5_000_000))).idle_ms_by_layer()
    assert bad["sched"] == 0.0 and good["sched"] > 0
    assert bad["arrival"] != pytest.approx(good["arrival"], rel=1e-3)
    assert sum(bad.values()) == pytest.approx(sum(good.values()))


def test_a_request_without_its_rpc_serve_raises(store):
    with pytest.raises(ValueError, match="rpc.serve"):
        spans.of(store(records(drop=("rs1",))))


def test_an_evicting_ring_raises(store):
    with pytest.raises(RuntimeError, match="evicted 3"):
        spans.of(store(records(), evicted=3))


def test_layer_of_names_every_span_of_the_path():
    assert [spans.layer_of(*sn) for sn in (
        ("router", "rpc.serve"), ("router", "router.merge"),
        ("router", "router.scatter"), ("ps", "rpc.decode"),
        ("ps", "ps.post"), ("ps", "microbatch.queue"), ("ps", "batch.pack"),
        ("ps", "engine.filter"), ("ps", "kernel.fused_scan_rerank"),
        ("ps", "engine.replace_raw"))] == [
        "router", "router", "ps", "ps", "ps", "sched", "sched",
        "engine", "engine", "engine"]


def test_trace_module_is_the_accepted_one():
    """These readers lean on the accepted reduction's names."""
    assert trace.MODULES_LINE == "XLA Modules"
    assert callable(trace.idle_gaps) and callable(trace.device_planes)
