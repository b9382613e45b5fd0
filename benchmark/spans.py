"""The program's own spans, for the traced run's readers.

The program keeps the finished spans of a profiled request in memory, on
`time.monotonic_ns()` (vearch_tpu/cluster/tracing.py `snapshot()`); the
harness put the device trace on the same clock through its mark
(`obs.trace_offset_ns`). `of(obs)` builds each request's tree once per
run and every reader under benchmark/metrics/ that reads spans takes its
number from it. Nothing here reads a clock.

Self time is the guide's: a span's duration minus the part of it that
its children cover, overlaps counted once.

On a program that has no `snapshot()` (the parent of the PR that added
it), with tracing off, or with an empty store, `of(obs)` is None and the
readers report nothing.
"""

from __future__ import annotations

import bisect
import json
import sys
from collections import defaultdict

from benchmark import cells, trace

ROUTER_SELF = ("rpc.serve", "rpc.decode", "rpc.encode", "router.search",
               "router.merge")
PS_SELF = ("rpc.serve", "rpc.decode", "rpc.encode", "ps.search", "ps.pre",
           "ps.gate_wait", "ps.post")
#: the PS's leaves that run on the handler thread and carry its CPU time
PS_HANDLER_LEAVES = ("rpc.decode", "ps.pre", "ps.post", "rpc.encode")
LAYERS = ("arrival", "router", "ps", "sched", "engine")


def snapshot():
    """(records, evicted) of this process's tracers, or None where the
    program has no span store to read."""
    try:
        from vearch_tpu.cluster import tracing
    except ImportError:
        return None
    if not hasattr(tracing, "snapshot"):
        return None
    return tracing.snapshot(), tracing.dropped()


def layer_of(service: str, name: str) -> str:
    """The layer a piece of idle time goes to when this span is the
    deepest one covering it."""
    if service == "router":
        # what of the scatter no PS span covers is the hop to the PS
        return "ps" if name == "router.scatter" else "router"
    if name in ("microbatch.queue", "batch.pack"):
        return "sched"
    if name.startswith(("rpc.", "ps.")):
        return "ps"
    return "engine"  # engine.*, kernel.* and the index's own phases


def covered_ns(lo: int, hi: int, intervals) -> int:
    """Length of [lo, hi) that the intervals cover, overlaps once."""
    return int(trace.union_ns(
        (max(a, lo), min(b, hi)) for a, b in intervals
        if min(b, hi) > max(a, lo)))


class Request:
    """One sampled search: the spans of one trace id, as a tree."""

    def __init__(self, trace_id: str, spans: list):
        self.trace_id = trace_id
        self.spans = spans
        self.kids = defaultdict(list)
        for s in spans:
            self.kids[s.parent_id].append(s)
        roots = [s for s in spans if s.parent_id is None
                 and s.service == "router" and s.name == "rpc.serve"]
        if len(roots) != 1:
            raise ValueError(
                f"trace {trace_id}: {len(roots)} rpc.serve roots at the "
                f"router among {sorted({s.name for s in spans})}")
        self.root = roots[0]
        self.depth = {}
        stack = [(self.root, 0)]
        while stack:
            s, d = stack.pop()
            self.depth[s.span_id] = d
            stack.extend((c, d + 1) for c in self.kids[s.span_id])
        self.t_search = min((s.t0_ns for s in spans
                             if s.name == "router.search"),
                            default=self.root.t0_ns)

    def self_ns(self, s) -> int:
        return (s.t1_ns - s.t0_ns) - covered_ns(
            s.t0_ns, s.t1_ns,
            [(c.t0_ns, c.t1_ns) for c in self.kids[s.span_id]])

    def self_ms(self, service: str, names) -> float:
        return sum(self.self_ns(s) for s in self.spans
                   if s.service == service and s.name in names) / 1e6

    def wait_ms(self) -> float:
        """Wall minus CPU over the PS's handler-thread leaves."""
        return sum(max(0, (s.t1_ns - s.t0_ns) - s.cpu_ns)
                   for s in self.spans
                   if s.service == "ps" and s.name in PS_HANDLER_LEAVES
                   and s.cpu_ns is not None and not self.kids[s.span_id]
                   ) / 1e6

    def kernels(self) -> list:
        return [s for s in self.spans if s.name.startswith("kernel.")]

    def layer_at(self, t_ns: float) -> str:
        """The layer of the deepest span covering t (the later-started of
        two at one depth: kernel.* inside engine.search.*)."""
        if t_ns < self.root.t0_ns:
            return "arrival"  # clients and sockets
        best = None
        for s in self.spans:
            if s.t0_ns <= t_ns < s.t1_ns and s.span_id in self.depth:
                key = (self.depth[s.span_id], s.t0_ns)
                if best is None or key > best[0]:
                    best = (key, s)
        return layer_of(best[1].service, best[1].name) if best else "arrival"

    def cut(self, lo_ns: float, hi_ns: float) -> dict:
        """[lo, hi) cut at this request's span boundaries, each piece
        given to the layer covering its middle: {layer: ns}."""
        edges = {lo_ns, hi_ns}
        for s in self.spans:
            edges.update(t for t in (s.t0_ns, s.t1_ns) if lo_ns < t < hi_ns)
        edges = sorted(edges)
        out = defaultdict(float)
        for a, b in zip(edges, edges[1:]):
            out[self.layer_at((a + b) / 2)] += b - a
        return out


class Analysis:
    def __init__(self, records: list, obs):
        by_trace = defaultdict(list)
        for r in records:
            by_trace[r.trace_id].append(r)
        #: every sampled search of the run (an upsert's or a process-level
        #: trace holds no router.search)
        self.requests = [
            Request(tid, spans) for tid, spans in by_trace.items()
            if any(s.name == "router.search" for s in spans)]
        lo, hi = obs.t0 * 1e9, (obs.t0 + obs.seconds) * 1e9
        #: those whose rpc.serve at the router ended inside the window
        self.window = [q for q in self.requests if lo <= q.root.t1_ns < hi]
        self.gc_ms = sum(
            covered_ns(int(lo), int(hi), [(r.t0_ns, r.t1_ns)])
            for r in records if r.name == "proc.gc") / 1e6
        self.obs = obs
        self._idle = self._dispatches = None

    def mean(self, per_request) -> float | None:
        values = [v for v in map(per_request, self.window) if v is not None]
        return sum(values) / len(values) if values else None

    # -- dispatches ----------------------------------------------------------

    def dispatches(self) -> dict:
        """{(name, t0_ns, t1_ns): [(request, kernel span), ...]}: a
        co-batched dispatch is replayed under each of its requests with
        one window, and counts once."""
        if self._dispatches is None:
            self._dispatches = defaultdict(list)
            for q in self.requests:
                for s in q.kernels():
                    self._dispatches[(s.name, s.t0_ns, s.t1_ns)].append(
                        (q, s))
        return self._dispatches

    def bucket_fill_pct(self) -> float | None:
        off = self.obs.trace_offset_ns
        rows = bucket = 0
        for (_name, t0, _t1), owners in self.dispatches().items():
            if self.obs.trace_lo_ns <= t0 - off < self.obs.trace_hi_ns:
                tags = owners[0][1].tags
                rows += tags.get("rows", 0)
                bucket += tags.get("bucket_rows", 0)
        return 100.0 * rows / bucket if bucket else None

    @staticmethod
    def launch_ms(q: Request, wait: bool = False) -> float | None:
        ks = [s for s in q.kernels() if "launch_us" in s.tags]
        if not ks:
            return None
        launch = sum(s.tags["launch_us"] for s in ks) / 1e3
        if not wait:
            return launch
        return sum(s.t1_ns - s.t0_ns for s in ks) / 1e6 - launch

    # -- the device's idle gaps, by layer --------------------------------------

    def module_owners(self, modules) -> list:
        """For each device program (name, start, end) of the trace, the
        request whose dispatch it is, or None. Only the configuration's
        serving program can be a dispatch: the mask's pad of the NEXT
        request starts within microseconds of a scan's end, inside the
        window of the kernel.* span that is still waiting for its
        device_get to return. Of the kernel.* spans whose window holds
        the program's start, the one that closes first after the
        program's end: its device_get returned for it (with four callers
        in flight four windows hold every start). A co-batched dispatch
        belongs to the request whose router.search started first."""
        off = self.obs.trace_offset_ns
        serving = cells.kernel(
            self.obs.config["serving"]["kernel"]).MODULE_SUBSTRING
        windows = sorted(
            ((t0, t1, min((q for q, _s in owners), key=lambda q: q.t_search))
             for (_n, t0, t1), owners in self.dispatches().items()),
            key=lambda w: w[:2])
        t0s = [w[0] for w in windows]
        out = []
        for name, m0, m1 in modules:
            held = [w for w in windows[:bisect.bisect_right(t0s, m0 + off)]
                    if w[1] >= m1 + off] if serving in name else []
            out.append(min(held, key=lambda w: w[1])[2] if held else None)
        return out

    def idle_ms_by_layer(self) -> dict:
        """Every idle gap of the traced window, cut at the span
        boundaries of the request that ended it: {layer: ms}, adding up
        to the device's idle time. The request that ended a gap is the
        owner of the first dispatch (module_owners) at or after the
        gap's end; a program before it that is no request's dispatch
        (the mask's pad, another thread's scan) is part of the same
        wait. What lies before that request reached the router, and a
        gap no dispatch ends, is `arrival`."""
        if self._idle is not None:
            return self._idle
        obs = self.obs
        off = obs.trace_offset_ns
        lo, hi = obs.trace_lo_ns, obs.trace_hi_ns
        planes = trace.device_planes(obs.trace)
        modules = sorted(((e[0], e[1], e[1] + e[2]) for e in
                          trace._line(planes[0], trace.MODULES_LINE)),
                         key=lambda m: m[1])
        starts = [m[1] for m in modules]
        # next_owner[i]: the owner of the first owned program from i on
        next_owner = [None] * (len(modules) + 1)
        for i, q in reversed(list(enumerate(self.module_owners(modules)))):
            next_owner[i] = q if q is not None else next_owner[i + 1]
        out = dict.fromkeys(LAYERS, 0.0)
        for g0, g1 in trace.idle_gaps(obs.trace, lo, hi, n=1_000_000):
            i = max(bisect.bisect_right(starts, g1) - 1, 0)
            if modules and modules[i][2] <= g1:
                i += 1  # the gap ends between programs, at the next one
            q = next_owner[i]
            if q is None:
                out["arrival"] += g1 - g0  # no dispatch ends it
                continue
            for layer, ns in q.cut(g0 + off, g1 + off).items():
                out[layer] += ns
        idle_ns = (hi - lo) - trace.busy_seconds(obs.trace, lo, hi) * 1e9
        if abs(sum(out.values()) - idle_ns) > 0.005 * (hi - lo):
            raise AssertionError(
                f"idle shares {out} add up to {sum(out.values())} ns, the "
                f"device was idle {idle_ns} ns")
        self._idle = {k: v / 1e6 for k, v in out.items()}
        return self._idle

    def idle_pct(self, layer: str) -> float | None:
        if not trace.device_planes(self.obs.trace):
            return None  # the CPU backend's trace has no device plane
        window_ms = (self.obs.trace_hi_ns - self.obs.trace_lo_ns) / 1e6
        return 100.0 * self.idle_ms_by_layer()[layer] / window_ms


def log_breakdown(a: Analysis, records: list) -> None:
    """One line on standard error, for PERF.md: what the metrics sum
    over. Mean self time and mean time not running (wall - CPU) per
    request by span, the process-level spans from the window's start on
    (and every engine.replace_raw), and where the slowest requests
    spent their time."""
    n = max(len(a.window), 1)
    self_ms, wait_ms = defaultdict(float), defaultdict(float)
    for q in a.window:
        for s in q.spans:
            key = f"{s.service}/{s.name}"
            self_ms[key] += q.self_ns(s) / 1e6 / n
            if s.cpu_ns is not None and not q.kids[s.span_id]:
                wait_ms[key] += max(0, s.t1_ns - s.t0_ns - s.cpu_ns) / 1e6 / n
    t0_ns = a.obs.t0 * 1e9
    process = [[r.name, round((r.t0_ns - t0_ns) / 1e9, 3),
                round((r.t1_ns - r.t0_ns) / 1e6, 3), r.tags]
               for r in records if r.t1_ns >= t0_ns
               and (r.name == "engine.replace_raw"  # a request may pay it
                    or r.parent_id is None and r.name != "rpc.serve")]
    slowest = []
    for q in sorted(a.window, key=lambda q: q.root.t0_ns - q.root.t1_ns)[:5]:
        top = sorted(q.spans, key=lambda s: -q.self_ns(s))[:3]
        slowest.append({
            "at_s": round((q.root.t0_ns - t0_ns) / 1e9, 3),
            "ms": round((q.root.t1_ns - q.root.t0_ns) / 1e6, 2),
            "top_self_ms": {f"{s.service}/{s.name}":
                            round(q.self_ns(s) / 1e6, 2) for s in top}})
    print(json.dumps({
        "msg": "spans", "requests_in_window": len(a.window),
        "spans": len(records),
        "self_ms": {k: round(v, 4) for k, v in sorted(self_ms.items())},
        "not_running_ms": {k: round(v, 4)
                           for k, v in sorted(wait_ms.items())},
        "process": sorted(process, key=lambda p: -p[2])[:40],
        "slowest": slowest}), file=sys.stderr, flush=True)


def of(obs) -> Analysis | None:
    """The run's analysis, made once; None when there is nothing to
    read (tracing off, a program without the store, no sampled span)."""
    if "_span_analysis" in obs.__dict__:
        return obs._span_analysis
    found = None
    if obs.trace is not None:
        snap = snapshot()
        if snap is not None and snap[0]:
            records, evicted = snap
            if evicted:
                raise RuntimeError(
                    f"the span rings evicted {evicted} spans: the readings "
                    f"would be of what was left")
            found = Analysis(records, obs)
            if not found.requests:
                found = None
            else:
                log_breakdown(found, records)
    obs._span_analysis = found
    return found
