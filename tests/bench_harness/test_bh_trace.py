"""The reduction from a device trace to numbers (benchmark/trace.py), on
a 60 ms cut of a real TPU v5e trace checked in beside this file, and on
hand-made intervals."""

import json
import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))
LO, HI = 1e6, 61e6  # the cut's window: the mark's span


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def test_union_counts_overlaps_once():
    assert trace.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert trace.union_ns([]) == 0


def test_clip_cuts_events_to_the_window():
    ev = [["a", 0, 10], ["b", 8, 10], ["c", 30, 5]]
    assert list(trace.clip(ev, 5, 20)) == [(5, 10), (8, 18)]


def hand_trace():
    ops = [["op_a", 0, 10], ["op_b", 10, 10], ["op_a", 40, 20]]
    mods = [["jit_scan(1)", 0, 20], ["jit_scan(1)", 40, 20],
            ["jit_other(2)", 70, 5]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [[trace.MARK, 0, 100]]}]}]}


def test_busy_idle_and_program_time_by_hand():
    tr = hand_trace()
    assert trace.busy_seconds(tr, 0, 100) == pytest.approx(40e-9)
    ev = trace.program_events(tr, "jit_scan", 0, 100)
    assert len(ev) == 2 and sum(e[2] for e in ev) == 40
    assert trace.top_ops(tr, 0, 100, 1) == [["op_a", pytest.approx(30e-9)]]
    assert trace.idle_gaps(tr, 0, 100, 2) == [(60, 100), (20, 40)]
    assert trace.mark_start_ns(tr) == 0


def test_gaps_are_named_by_requests_in_flight():
    named = trace.name_gaps([(20, 40), (60, 100)], offset_ns=1000,
                            t_send=[1010e-9], t_done=[1050e-9])
    assert named[0] == ["no request in flight (waiting for the clients)",
                        pytest.approx(40e-9)]
    assert named[1] == [
        "requests in flight (host path: router/PS/scheduler/engine)",
        pytest.approx(20e-9)]


def test_no_device_plane_reads_as_nothing_not_zero():
    tr = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert trace.busy_seconds(tr, 0, 10) is None
    assert trace.idle_gaps(tr, 0, 10) == []


def test_recorded_trace_busy_union_and_idle_share(recorded):
    busy = trace.busy_seconds(recorded, LO, HI)
    assert busy == pytest.approx(0.057705079, rel=1e-6)
    assert 100 * (1 - busy / 0.060) == pytest.approx(3.82, abs=0.01)
    gaps = trace.idle_gaps(recorded, LO, HI, 3)
    assert gaps[0] == (pytest.approx(58851448.0), HI)


def test_recorded_trace_per_program_time(recorded):
    ev = trace.program_events(recorded, "int8_scan_rerank", LO, HI)
    assert len(ev) == 6
    assert sum(e[2] for e in ev) == pytest.approx(54880680.0)
    assert trace.program_events(recorded, "no_such_program", LO, HI) == []
    top = trace.top_ops(recorded, LO, HI, 2)
    assert top[0][0].startswith("%custom-call") and "TopK" not in top[1][0]
    assert top[0][1] == pytest.approx(0.022959591)
    assert trace.mark_start_ns(recorded) == 1e6
