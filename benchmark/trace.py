"""Reduction from a profiler trace to device numbers.

`compact()` turns the profiler's .xplane.pb into a plain dict
({"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}), which is also the form of the small recorded trace
the unit tests check this file against. Everything else here works on
that dict and reads no clock.

On a TPU each chip is a plane "/device:TPU:<i>"; its line "XLA Modules"
holds one event per dispatched program (named after the jitted
function) and "XLA Ops" one per operation inside it.
"""

from __future__ import annotations

import glob
import os

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
#: the harness's own marker: a host span whose start it also read on
#: time.monotonic_ns(), to put the trace and the clients on one clock
MARK = "bench_mark"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def compact(xplane_path: str, device_prefix: str = "/device:") -> dict:
    """Device planes whole; of host planes only the lines that hold the
    harness's marker (host planes are most of a trace's bulk)."""
    from jax.profiler import ProfileData

    out = {"planes": []}
    for plane in ProfileData.from_file(xplane_path).planes:
        is_dev = plane.name.startswith(device_prefix)
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            if is_dev or any(e[0] == MARK for e in events):
                if not is_dev:
                    events = [e for e in events if e[0] == MARK]
                lines.append({"name": line.name, "events": events})
        if lines:
            out["planes"].append({"name": plane.name, "lines": lines})
    return out


def device_planes(trace: dict, device_prefix: str = "/device:") -> list[dict]:
    return [p for p in trace["planes"] if p["name"].startswith(device_prefix)
            and any(ln["events"] for ln in p["lines"])]


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def op_events(plane: dict) -> list:
    """Events during which an operation ran on the device."""
    return _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)


def union_ns(intervals) -> float:
    """Total length covered by [start, end) intervals, overlaps once."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def clip(events, lo: float, hi: float):
    """[start, end) of each event, cut to the window [lo, hi)."""
    for _name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield a, b


def busy_seconds(trace: dict, lo_ns: float, hi_ns: float) -> float | None:
    """Seconds in which an operation ran, averaged over the chips."""
    planes = device_planes(trace)
    if not planes:
        return None
    return sum(union_ns(clip(op_events(p), lo_ns, hi_ns))
               for p in planes) / len(planes) / 1e9


def program_events(trace: dict, substring: str, lo_ns: float, hi_ns: float):
    """Dispatches of one program (an XLA module whose name holds
    `substring`) that started inside the window, over all chips."""
    return [e for p in device_planes(trace)
            for e in _line(p, MODULES_LINE)
            if substring in e[0] and lo_ns <= e[1] < hi_ns]


def top_ops(trace: dict, lo_ns: float, hi_ns: float, n: int = 10):
    """[[name, seconds], ...] of the operations that took most time."""
    total: dict[str, float] = {}
    for p in device_planes(trace):
        for name, start, dur in op_events(p):
            if lo_ns <= start < hi_ns:
                total[name] = total.get(name, 0.0) + dur / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, lo_ns: float, hi_ns: float, n: int = 10):
    """The n longest [start_ns, end_ns) gaps of the first chip in which
    no operation ran."""
    planes = device_planes(trace)
    if not planes:
        return []
    spans = sorted(clip(op_events(planes[0]), lo_ns, hi_ns))
    gaps, edge = [], lo_ns
    for a, b in spans:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    if hi_ns > edge:
        gaps.append((edge, hi_ns))
    return sorted(gaps, key=lambda g: g[0] - g[1])[:n]


def mark_start_ns(trace: dict) -> float | None:
    for p in trace["planes"]:
        for ln in p["lines"]:
            for name, start, _dur in ln["events"]:
                if name == MARK:
                    return start
    return None


def name_gaps(gaps, offset_ns: float, t_send, t_done):
    """Total idle seconds by what the clients saw the host doing at each
    gap's middle: nothing asked (no request sent and not yet answered), or
    requests in flight, which the host path was then working on.
    `offset_ns` maps trace time to the clients' monotonic clock."""
    import numpy as np

    t_send = np.asarray(t_send, float) * 1e9
    t_done = np.asarray(t_done, float) * 1e9
    waiting = "no request in flight (waiting for the clients)"
    working = "requests in flight (host path: router/PS/scheduler/engine)"
    named = {working: 0.0, waiting: 0.0}
    for lo, hi in gaps:
        mid = (lo + hi) / 2 + offset_ns
        busy = bool(((t_send <= mid) & (t_done > mid)).any())
        named[working if busy else waiting] += (hi - lo) / 1e9
    return [[k, v] for k, v in sorted(named.items(), key=lambda kv: -kv[1])
            if v > 0]
