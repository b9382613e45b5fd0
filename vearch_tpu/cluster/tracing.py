"""Distributed tracing: spans, cross-process propagation, local store,
and an OTLP-HTTP exporter to a real collector.

The reference wires Jaeger/opentracing end-to-end (reference:
cmd/vearch/startup.go:66-85 initJaeger; ps/handler_document.go:123-126
extracts the span context from rpcx metadata; router request-id
middleware, router/server.go:63-80). Each process keeps a bounded ring
of finished spans, queryable via `GET /debug/traces` on every role and
through `snapshot()` in-process, an optional JSONL file written when the
role stops, and — when `[tracer] collector_endpoint` is set — ships
batches as OTLP/HTTP JSON (`POST {endpoint}/v1/traces`), the wire shape
Jaeger >=1.35 and every OTel collector ingest natively (the modern
equivalent of the reference's jaeger-agent UDP path).

Propagation rides the request envelope (`_trace_ctx` in the RPC body) —
the envelope is this framework's rpcx-metadata equivalent; handlers
never see transport headers.

A span is sampled when the client asked (`trace: true`, `profile: true`)
or the role's `trace_sample` probability fires (reference: sampler
type/param from the [tracer] config block).

One clock: a span keeps `time.monotonic_ns()` stamps (`t0_ns`, `t1_ns`),
the clock a `jax.profiler` trace can be put on through a marker, and
becomes epoch microseconds only where it is read (`to_dict`,
`span_to_otlp`), through the one offset in `vearch_tpu.utils`.
"""

from __future__ import annotations

import gc
import json
import random
import threading
import time
import weakref
from collections import deque
from typing import Any, NamedTuple

from vearch_tpu.utils import epoch_us_to_mono_ns, mono_ns_to_epoch_us

#: finished spans a Tracer keeps before it evicts the oldest. Reckoned
#: from the busiest profiled traffic the benchmark offers: 200
#: requests/s x 14 spans a request at the PS x ~30 s (window, drain and
#: the write check after it) = 84,000; the next power of two. A full
#: ring holds about 60 MB (docs/OBSERVABILITY.md); an unsampled role
#: holds a handful of process-level spans.
DEFAULT_MAX_SPANS = 131_072


# ids come from a generator of this module's own, seeded from the
# operating system at import: a caller that seeds the global `random`
# must not make two roles mint the same ids
_ids = random.Random()


def _new_span_id() -> str:
    return f"{_ids.getrandbits(64):016x}"


def _new_trace_id() -> str:
    return f"{_ids.getrandbits(128):032x}"  # 32 hex digits (OTLP)


class Span:
    """One timed window. Entered and left on one thread it also carries
    that thread's CPU time (`cpu_ns`): on a span without children, wall
    minus CPU is time the thread was not running (waiting for the
    interpreter lock, a lock, or I/O). Replayed windows (`Tracer.record`)
    have `cpu_ns` None unless the recorder measured it."""

    __slots__ = (
        "tracer", "trace_id", "span_id", "parent_id", "name", "service",
        "t0_ns", "t1_ns", "cpu_ns", "tags", "status", "_tid", "_cpu0",
    )

    def __init__(self, tracer: "Tracer", name: str, trace_id: str,
                 parent_id: str | None, tags: dict | None,
                 span_id: str | None = None, t0_ns: int | None = None):
        self.tracer = tracer
        self.trace_id = trace_id
        self.span_id = span_id or _new_span_id()
        self.parent_id = parent_id
        self.name = name
        self.service = tracer.service
        self.tags: dict[str, Any] = dict(tags) if tags else {}
        self.status = "ok"
        self.cpu_ns: int | None = None
        if t0_ns is None:  # live; the CPU window lies inside the wall one
            self._tid = threading.get_ident()
            t0_ns = time.monotonic_ns()
            self._cpu0 = time.thread_time_ns()
        else:  # a window measured elsewhere
            self._tid = self._cpu0 = 0
        self.t0_ns = self.t1_ns = t0_ns

    def set_tag(self, key: str, value: Any) -> None:
        self.tags[key] = value

    def ctx(self) -> dict:
        """The propagation payload for downstream RPC bodies."""
        return {"trace_id": self.trace_id, "parent": self.span_id}

    def child(self, name: str, tags: dict | None = None) -> "Span":
        return Span(self.tracer, name, self.trace_id, self.span_id, tags)

    def finish(self) -> None:
        if self._tid == threading.get_ident():
            self.cpu_ns = time.thread_time_ns() - self._cpu0
        self.t1_ns = time.monotonic_ns()
        self.tracer._finish(self)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None:
            self.status = f"error: {type(exc).__name__}"
        self.finish()

    @property
    def start_us(self) -> int:
        return mono_ns_to_epoch_us(self.t0_ns)

    @property
    def dur_us(self) -> int:
        return (self.t1_ns - self.t0_ns) // 1000

    def to_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "service": self.service,
            "start_us": self.start_us,
            "duration_us": self.dur_us,
            "tags": self.tags,
            "status": self.status,
        }


class SpanRecord(NamedTuple):
    """A finished span as `snapshot()` hands it out: plain values on the
    monotonic clock, nothing of the tracer."""

    service: str
    name: str
    trace_id: str
    span_id: str
    parent_id: str | None
    t0_ns: int
    t1_ns: int
    cpu_ns: int | None
    tags: dict


def _otlp_attr(key: str, value: Any) -> dict:
    if isinstance(value, bool):
        return {"key": key, "value": {"boolValue": value}}
    if isinstance(value, int):
        return {"key": key, "value": {"intValue": str(value)}}
    if isinstance(value, float):
        return {"key": key, "value": {"doubleValue": value}}
    return {"key": key, "value": {"stringValue": str(value)}}


def span_to_otlp(span: Span) -> dict:
    """Finished span -> OTLP JSON span object."""
    start_ns = span.start_us * 1000
    return {
        "traceId": span.trace_id,
        "spanId": span.span_id,
        "parentSpanId": span.parent_id or "",
        "name": span.name,
        "kind": 2,  # SPAN_KIND_SERVER
        "startTimeUnixNano": str(start_ns),
        "endTimeUnixNano": str(start_ns + span.dur_us * 1000),
        "attributes": [_otlp_attr(k, v) for k, v in span.tags.items()],
        "status": (
            {"code": 1} if span.status == "ok"
            else {"code": 2, "message": str(span.status)}
        ),
    }


class OtlpHttpExporter:
    """Batching OTLP/HTTP JSON shipper (stdlib urllib, background
    thread). Export never blocks the request path: spans are queued and
    flushed every `flush_interval` seconds or `max_batch` spans; a dead
    collector costs a dropped batch and a counter, not latency."""

    def __init__(self, endpoint: str, service: str,
                 flush_interval: float = 2.0, max_batch: int = 512,
                 timeout: float = 5.0):
        self.url = endpoint.rstrip("/") + "/v1/traces"
        self.service = service
        self.flush_interval = float(flush_interval)
        self.max_batch = int(max_batch)
        self.timeout = float(timeout)
        self.dropped = 0
        self.exported = 0
        self._q: deque[Span] = deque(maxlen=8192)
        self._cond = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, daemon=True,
            name=f"otlp-export-{service}",
        )
        self._thread.start()

    def export(self, span: Span) -> None:
        with self._cond:
            if len(self._q) == self._q.maxlen:
                self.dropped += 1  # eviction is loss too, count it
            self._q.append(span)
            if len(self._q) >= self.max_batch:
                self._cond.notify()

    def _loop(self) -> None:
        while True:
            with self._cond:
                self._cond.wait(self.flush_interval)
                batch = list(self._q)
                self._q.clear()
                if self._stop and not batch:
                    return
            if batch:
                self._send(batch)

    def _send(self, batch: list[Span]) -> None:
        import urllib.request

        body = json.dumps({
            "resourceSpans": [{
                "resource": {"attributes": [
                    _otlp_attr("service.name", self.service),
                ]},
                "scopeSpans": [{
                    "scope": {"name": "vearch_tpu"},
                    "spans": [span_to_otlp(sp) for sp in batch],
                }],
            }],
        }).encode()
        req = urllib.request.Request(
            self.url, data=body, method="POST",
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=self.timeout):
                pass
            self.exported += len(batch)
        except Exception:
            self.dropped += len(batch)

    def flush(self) -> None:
        """Synchronous drain for shutdown/tests (bounded by the
        constructor's send timeout)."""
        with self._cond:
            batch = list(self._q)
            self._q.clear()
        if batch:
            self._send(batch)

    def close(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify()
        # the loop thread may be mid-_send with spans it already drained
        # from the queue — join it (bounded) or shutdown kills the POST
        self._thread.join(self.timeout + 1.0)
        self.flush()


#: every live Tracer of this process, for snapshot(): router and PS
#: share one process in StandaloneCluster and in the benchmark
_TRACERS: "weakref.WeakSet[Tracer]" = weakref.WeakSet()


class ServeSlot:
    """What the RPC server offers the handler it is about to call
    (`rpc._serve`): the id its `rpc.serve` span will have, so that the
    handler's root span can name it as parent before it exists. The
    root that takes the offer (`Tracer.span(..., serve_root=True)`)
    leaves itself here, and `_serve` records `rpc.serve` and its
    `rpc.decode` / `rpc.encode` leaves retroactively around it. A
    handler that samples nothing leaves `root` None and nothing is
    recorded."""

    __slots__ = ("span_id", "parent_id", "root")

    def __init__(self) -> None:
        self.span_id = _new_span_id()
        self.parent_id: str | None = None
        self.root: Span | None = None


_serve_tls = threading.local()


def offer_serve_slot() -> ServeSlot:
    slot = _serve_tls.slot = ServeSlot()
    return slot


def withdraw_serve_slot() -> None:
    _serve_tls.slot = None


class Tracer:
    """Per-process span factory + bounded finished-span store."""

    def __init__(self, service: str, max_spans: int = DEFAULT_MAX_SPANS,
                 sample_rate: float = 0.0, export_path: str | None = None,
                 collector_endpoint: str | None = None):
        self.service = service
        self.sample_rate = float(sample_rate)
        self.export_path = export_path
        self.exporter = (
            OtlpHttpExporter(collector_endpoint, service)
            if collector_endpoint else None
        )
        #: spans the ring evicted to make room
        self.dropped = 0
        #: the one trace that process-level spans (proc.gc, ps.flush,
        #: an unrequested engine.replace_raw) hang under
        self.process_trace_id = _new_trace_id()
        self._spans: deque[Span] = deque(maxlen=max_spans)
        # finishers never wait: whoever finds the lock taken (a reader
        # copying the ring, or this very thread when a collection fires
        # under it: proc.gc) leaves the span here for the next holder
        self._late: deque[Span] = deque()
        self._lock = threading.Lock()
        _TRACERS.add(self)

    def should_sample(self, explicit: bool) -> bool:
        return explicit or (
            self.sample_rate > 0 and random.random() < self.sample_rate
        )

    def process_ctx(self) -> dict:
        """`ctx` of a span that belongs to the process, not a request."""
        return {"trace_id": self.process_trace_id, "parent": None}

    def span(self, name: str, ctx: dict | None = None,
             tags: dict | None = None, serve_root: bool = False) -> Span:
        """Start a span; `ctx` is an incoming `_trace_ctx` payload (or
        None for a root span). `serve_root` marks the root span of an
        RPC handler: it becomes the child of the `rpc.serve` span the
        server records around the handler (see ServeSlot)."""
        trace_id = (ctx or {}).get("trace_id") or _new_trace_id()
        parent = (ctx or {}).get("parent")
        slot = getattr(_serve_tls, "slot", None) if serve_root else None
        if slot is None:
            return Span(self, name, trace_id, parent, tags)
        sp = Span(self, name, trace_id, slot.span_id, tags)
        if slot.root is None:  # a handler's re-run adds a sibling root
            slot.parent_id, slot.root = parent, sp
        return sp

    def record(self, name: str, ctx: dict | None = None,
               start_us: int | None = None, dur_us: int = 0,
               tags: dict | None = None, status: str = "ok", *,
               t0_ns: int | None = None, t1_ns: int | None = None,
               cpu_ns: int | None = None,
               span_id: str | None = None) -> Span:
        """Emit an already-measured span retroactively.

        The window is `[t0_ns, t1_ns]` on time.monotonic_ns(), or the
        engine's `start_us` / `dur_us` form (epoch microseconds through
        utils.mono_us), or, with neither, `dur_us` ending now.

        The engine measures its phase windows inline (no tracer in
        scope) and ships them up as `[name, start_us, dur_us]` rows; the
        PS replays them here as child spans with their REAL wall
        windows, so /debug/traces shows coarse-quantize/scan/rerank
        timing nested under ps.search. Also used for rare raft events
        (elections, snapshot installs) that have no request context."""
        if t0_ns is None:
            dur_ns = max(int(dur_us), 0) * 1000
            t0_ns = (epoch_us_to_mono_ns(int(start_us))
                     if start_us is not None
                     else time.monotonic_ns() - dur_ns)
            t1_ns = t0_ns + dur_ns
        elif t1_ns is None:
            t1_ns = t0_ns
        trace_id = (ctx or {}).get("trace_id") or _new_trace_id()
        parent = (ctx or {}).get("parent")
        sp = Span(self, name, trace_id, parent, tags, span_id=span_id,
                  t0_ns=t0_ns)
        sp.t1_ns = max(t1_ns, t0_ns)
        sp.cpu_ns = cpu_ns
        sp.status = status
        self._finish(sp)
        return sp

    def _admit(self, span: Span) -> None:
        if len(self._spans) == self._spans.maxlen:
            self.dropped += 1
        self._spans.append(span)

    def _drain_late(self) -> None:
        while self._late:
            self._admit(self._late.popleft())

    def _finish(self, span: Span) -> None:
        if self._lock.acquire(blocking=False):
            try:
                self._admit(span)
                self._drain_late()
            finally:
                self._lock.release()
        else:
            self._late.append(span)
        if self.exporter is not None:
            self.exporter.export(span)

    def finished(self) -> list[Span]:
        """The ring's spans, oldest first."""
        with self._lock:
            self._drain_late()
            return list(self._spans)

    def spans(self, trace_id: str | None = None,
              limit: int = 200) -> list[dict]:
        items = self.finished()
        if trace_id:
            items = [s for s in items if s.trace_id == trace_id]
        return [s.to_dict() for s in items[-limit:]]

    def close(self) -> None:
        """Ship what is buffered: the collector's last batch, and the
        ring as JSONL to `export_path`, in ONE write (never per span on
        the request path). The ring stays readable afterwards."""
        if self.exporter is not None:
            self.exporter.close()
        if self.export_path:
            lines = [json.dumps(s.to_dict()) + "\n" for s in self.finished()]
            try:
                with open(self.export_path, "a") as f:
                    f.writelines(lines)
            except OSError:
                pass


def snapshot() -> list[SpanRecord]:
    """The finished spans of every Tracer of this process, on the
    monotonic clock. Still answers after the roles stopped."""
    return [
        SpanRecord(s.service, s.name, s.trace_id, s.span_id, s.parent_id,
                   s.t0_ns, s.t1_ns, s.cpu_ns, s.tags)
        for tr in list(_TRACERS) for s in tr.finished()
    ]


def dropped() -> int:
    """Spans the rings of this process evicted; 0 means snapshot() is
    everything that was sampled."""
    return sum(tr.dropped for tr in list(_TRACERS))


class GcSpans:
    """`gc.callbacks` hook: a `proc.gc` span per collection of
    generation 2 and per any collection longer than a millisecond, under
    the tracer's process-level trace, sampled or not: a full collection
    over a million-key table stalls every request of the process and
    belongs to none of them."""

    MIN_NS = 1_000_000

    def __init__(self, tracer: Tracer):
        self.tracer = tracer
        self._t0_ns = 0

    def install(self) -> None:
        """One hook a process: a second PS of the same process (tests)
        would record every collection twice."""
        if not any(isinstance(getattr(cb, "__self__", None), GcSpans)
                   for cb in gc.callbacks):
            gc.callbacks.append(self._on_gc)

    def remove(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        # collections do not nest and run under the interpreter lock:
        # one start stamp is enough
        if phase == "start":
            self._t0_ns = time.monotonic_ns()
            return
        t1_ns = time.monotonic_ns()
        if info["generation"] == 2 or t1_ns - self._t0_ns >= self.MIN_NS:
            self.tracer.record(
                "proc.gc", ctx=self.tracer.process_ctx(),
                t0_ns=self._t0_ns, t1_ns=t1_ns,
                tags={"generation": info["generation"],
                      "collected": info["collected"]},
            )


class SlowLog:
    """Per-role bounded ring of slow-request records, served at
    `GET /debug/slowlog` (reference: the PS slow-request marking around
    engine slow_search_time + the router access log's slow entries —
    here a structured ring instead of grep-able text).

    `threshold_ms <= 0` disables slow capture; killed requests are
    force-recorded regardless (a request the operator or a deadline had
    to abort is exactly what the slowlog exists to explain). Entries
    carry the PR-2 phase breakdown when the role has one in hand —
    schema in docs/OBSERVABILITY.md."""

    def __init__(self, maxlen: int = 256, threshold_ms: float = 0.0):
        self.threshold_ms = float(threshold_ms)
        self._entries: deque[dict] = deque(maxlen=maxlen)
        self._lock = threading.Lock()

    def should_log(self, elapsed_ms: float, killed: bool = False) -> bool:
        return killed or (
            self.threshold_ms > 0 and elapsed_ms > self.threshold_ms
        )

    def add(self, entry: dict) -> None:
        e = dict(entry)
        e.setdefault("ts", time.time())  # lint: allow[wall-clock] operator-facing slowlog stamp, display-only
        with self._lock:
            self._entries.append(e)

    def entries(self, limit: int = 100) -> list[dict]:
        with self._lock:
            items = list(self._entries)
        return items[-max(int(limit), 0):]

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


class NullSpan:
    """No-op stand-in so call sites stay branch-free."""

    trace_id = ""
    span_id = ""

    def set_tag(self, key, value):
        pass

    def ctx(self):
        return None

    def child(self, name, tags=None):
        return self

    def finish(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        pass


NULL_SPAN = NullSpan()
