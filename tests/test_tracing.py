"""Distributed tracing: spans, cross-process propagation, /debug/traces.

Reference: Jaeger end-to-end (cmd/vearch/startup.go:66 initJaeger;
ps/handler_document.go:123 span-context extraction from rpcx metadata).
Here: span trees propagated via the RPC envelope, stored per-process,
queryable on every role."""

import json
import time
import urllib.request

import numpy as np
import pytest

from vearch_tpu.cluster.tracing import Tracer


class TestTracer:
    def test_span_tree_and_store(self):
        tr = Tracer("svc")
        with tr.span("root", tags={"a": 1}) as root:
            with tr.span("child", ctx=root.ctx()) as child:
                child.set_tag("b", 2)
        spans = tr.spans()
        assert len(spans) == 2
        by_name = {s["name"]: s for s in spans}
        assert by_name["child"]["parent_id"] == by_name["root"]["span_id"]
        assert by_name["child"]["trace_id"] == by_name["root"]["trace_id"]
        assert by_name["root"]["tags"] == {"a": 1}
        assert by_name["child"]["duration_us"] >= 0

    def test_error_status(self):
        tr = Tracer("svc")
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert tr.spans()[0]["status"].startswith("error")

    def test_sampling(self):
        tr = Tracer("svc", sample_rate=0.0)
        assert not tr.should_sample(False)
        assert tr.should_sample(True)  # explicit trace:true always wins
        tr2 = Tracer("svc", sample_rate=1.0)
        assert tr2.should_sample(False)

    def test_filter_by_trace_id(self):
        tr = Tracer("svc")
        with tr.span("a") as sa:
            pass
        with tr.span("b"):
            pass
        only = tr.spans(trace_id=sa.trace_id)
        assert len(only) == 1 and only[0]["name"] == "a"

    def test_jsonl_export(self, tmp_path):
        """`export_path` is written once, at close: finishing a span
        opens no file."""
        import os

        path = str(tmp_path / "spans.jsonl")
        tr = Tracer("svc", export_path=path)
        for name in ("exported", "second"):
            with tr.span(name):
                pass
        assert not os.path.exists(path)
        tr.close()
        rows = [json.loads(line) for line in open(path)]
        assert [r["name"] for r in rows] == ["exported", "second"]
        assert rows[0]["service"] == "svc"
        assert len(tr.spans()) == 2  # the ring is still readable

    def test_ring_evicts_oldest_and_counts_dropped(self):
        tr = Tracer("svc", max_spans=3)
        for i in range(5):
            with tr.span(f"s{i}"):
                pass
        assert [s["name"] for s in tr.spans()] == ["s2", "s3", "s4"]
        assert tr.dropped == 2

    def test_monotonic_stamps_cpu_time_and_one_epoch_offset(self):
        from vearch_tpu.cluster import tracing
        from vearch_tpu.utils import mono_ns_to_epoch_us, mono_us

        tr = Tracer("svc")
        before = time.monotonic_ns()
        with tr.span("live") as sp:
            sum(range(20000))
        after = time.monotonic_ns()
        assert before <= sp.t0_ns <= sp.t1_ns <= after
        # the CPU window lies inside the wall window, on one thread
        assert 0 < sp.cpu_ns <= sp.t1_ns - sp.t0_ns
        assert sp.to_dict()["start_us"] == mono_ns_to_epoch_us(sp.t0_ns)
        # a replayed engine row comes back through the same offset
        t = time.monotonic()
        rec = tr.record("row", start_us=mono_us(t), dur_us=250)
        assert abs(rec.t0_ns - int(t * 1e9)) < 2000
        assert rec.t1_ns - rec.t0_ns == 250_000 and rec.cpu_ns is None
        assert len(sp.span_id) == 16 and len(sp.trace_id) == 32
        got = [r for r in tracing.snapshot() if r.trace_id == sp.trace_id]
        assert [(r.service, r.name, r.t0_ns, r.cpu_ns) for r in got] == [
            ("svc", "live", sp.t0_ns, sp.cpu_ns)]

    def test_gc_hook_records_process_level_spans(self):
        import gc

        from vearch_tpu.cluster.tracing import GcSpans

        tr = Tracer("svc")
        hook = GcSpans(tr)
        others, gc.callbacks[:] = gc.callbacks[:], []  # a live PS's hook
        hook.install()
        try:
            second = GcSpans(Tracer("other"))
            second.install()  # one hook a process: this one stays out
            assert second._on_gc not in gc.callbacks
            gc.collect(2)
        finally:
            hook.remove()
            gc.callbacks[:] = others
        found = [s for s in tr.spans() if s["name"] == "proc.gc"
                 and s["tags"]["generation"] == 2]
        assert len(found) == 1
        assert found[0]["trace_id"] == tr.process_trace_id
        assert found[0]["parent_id"] is None
        assert "collected" in found[0]["tags"]
        gc.collect(2)
        assert len([s for s in tr.spans() if s["name"] == "proc.gc"
                    and s["tags"]["generation"] == 2]) == 1  # removed


def _wait_for_serve_span(tracer, trace_id: str,
                         name: str = "rpc.serve") -> None:
    """rpc.serve and its leaves are recorded after the reply went out
    (the window ends past the write; rpc.encode comes last), so a
    client can hold the answer a moment before the server holds the
    spans."""
    deadline = time.monotonic() + 5.0
    while not any(s["name"] == name
                  for s in tracer.spans(trace_id=trace_id, limit=10_000)):
        assert time.monotonic() < deadline, f"no {name} span appeared"
        time.sleep(0.005)


def _fetch_traces(addr: str, trace_id: str) -> list[dict]:
    with urllib.request.urlopen(
        f"http://{addr}/debug/traces?trace_id={trace_id}"
    ) as r:
        return json.loads(r.read())["spans"]


class _MockCollector:
    """Stdlib OTLP/HTTP collector: records POST /v1/traces bodies."""

    def __init__(self):
        import http.server
        import threading

        collector = self

        class H(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                collector.batches.append(json.loads(self.rfile.read(n)))
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *a):
                pass

        self.batches: list[dict] = []
        self.httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.endpoint = f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def spans(self) -> list[dict]:
        out = []
        for b in self.batches:
            for rs in b.get("resourceSpans", []):
                svc = next((
                    a["value"]["stringValue"]
                    for a in rs["resource"]["attributes"]
                    if a["key"] == "service.name"), "?")
                for ss in rs.get("scopeSpans", []):
                    for s in ss.get("spans", []):
                        out.append({**s, "service": svc})
        return out

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()  # free the port: connects now refused


def test_otlp_exporter_ships_span_tree(tmp_path, rng):
    """A real collector endpoint receives a correctly-parented
    router->PS span tree as OTLP/HTTP JSON (VERDICT r2 #7; reference
    ships the same tree to jaeger-agent, startup.go:66-85)."""
    from vearch_tpu.cluster.master import MasterServer
    from vearch_tpu.cluster.ps import PSServer
    from vearch_tpu.cluster.router import RouterServer
    from vearch_tpu.sdk.client import VearchClient
    import vearch_tpu.cluster.rpc as rpc

    col = _MockCollector()
    master = MasterServer()
    master.start()
    ps = PSServer(data_dir=str(tmp_path / "tr"), master_addr=master.addr,
                  trace_collector=col.endpoint)
    ps.start()
    router = RouterServer(master_addr=master.addr,
                          trace_collector=col.endpoint)
    router.start()
    try:
        cl = VearchClient(router.addr)
        cl.create_database("t")
        cl.create_space("t", {
            "name": "s", "partition_num": 2,
            "fields": [{"name": "v", "data_type": "vector", "dimension": 16,
                        "index": {"index_type": "FLAT", "metric_type": "L2",
                                  "params": {}}}],
        })
        vecs = rng.standard_normal((30, 16)).astype(np.float32)
        cl.upsert("t", "s", [{"_id": f"d{i}", "v": vecs[i]}
                             for i in range(30)])
        out = rpc.call(router.addr, "POST", "/document/search", {
            "db_name": "t", "space_name": "s",
            "vectors": [{"field": "v", "feature": vecs[3].tolist()}],
            "limit": 3, "trace": True,
        })
        tid = out["trace_id"]
        _wait_for_serve_span(router.tracer, tid)
        router.tracer.exporter.flush()
        ps.tracer.exporter.flush()

        got = [s for s in col.spans() if s["traceId"] == tid]
        names = {s["name"] for s in got}
        assert "router.search" in names and "ps.search" in names, names
        root = next(s for s in got if s["name"] == "router.search")
        serve = next(s for s in got if s["name"] == "rpc.serve"
                     and s["service"] == "router")
        assert serve["parentSpanId"] == ""  # true root
        assert root["parentSpanId"] == serve["spanId"]
        scatter = [s for s in got if s["name"] == "router.scatter"]
        assert len(scatter) == 2
        for s in scatter:
            assert s["service"] == "router"
            assert s["parentSpanId"] == root["spanId"]
        scatter_ids = {s["spanId"] for s in scatter}
        ps_search = [s for s in got
                     if s["service"] == "ps" and s["name"] == "ps.search"]
        assert len(ps_search) == 2  # one per partition
        ps_search_ids = {s["spanId"] for s in ps_search}
        ps_serve_ids = {s["spanId"] for s in got if s["service"] == "ps"
                        and s["name"] == "rpc.serve"}
        assert len(ps_serve_ids) == 2
        for s in (ss for ss in got if ss["service"] == "ps"):
            if s["name"] == "rpc.serve":
                assert s["parentSpanId"] in scatter_ids
            elif s["name"] in ("ps.search", "rpc.decode", "rpc.encode"):
                assert s["parentSpanId"] in ps_serve_ids
            else:
                # engine/kernel phase spans nest under their ps.search
                assert s["parentSpanId"] in ps_search_ids
            # OTLP shape essentials survive the wire
            assert len(s["traceId"]) == 32 and len(s["spanId"]) == 16
            assert int(s["endTimeUnixNano"]) >= int(s["startTimeUnixNano"])
            assert s["status"]["code"] == 1
        assert router.tracer.exporter.exported >= 3
        assert router.tracer.exporter.dropped == 0
    finally:
        router.stop()
        ps.stop()
        master.stop()
        col.close()


def test_otlp_exporter_collector_killed_mid_batch():
    """Collector outage mid-run: spans shipped before the kill count as
    exported, spans after it count as dropped, and neither span creation
    nor the ring store is affected. The request path must never pay for
    collector health (observability satellite)."""
    col = _MockCollector()
    tr = Tracer("svc", collector_endpoint=col.endpoint)
    with tr.span("before"):
        pass
    tr.exporter.flush()
    assert tr.exporter.exported >= 1
    assert tr.exporter.dropped == 0
    assert any(s["name"] == "before" for s in col.spans())

    col.close()  # collector dies with spans still being produced

    t0 = time.monotonic()
    for i in range(64):
        with tr.span(f"after-{i}"):
            pass
    # creation is queue-append only — a dead collector adds no latency
    assert time.monotonic() - t0 < 1.0
    tr.exporter.flush()
    assert tr.exporter.dropped >= 64
    assert tr.exporter.exported >= 1  # pre-kill batch still counted
    # local ring store keeps every span regardless of collector health
    assert len(tr.spans()) == 65
    # queue stays bounded: sustained outage evicts, never grows
    assert len(tr.exporter._q) == 0


def test_otlp_exporter_survives_dead_collector():
    """A dead collector must cost dropped batches, never request-path
    errors or blocking."""
    tr = Tracer("svc", collector_endpoint="http://127.0.0.1:9")  # closed
    with tr.span("a"):
        pass
    tr.exporter.flush()
    assert tr.exporter.dropped == 1
    assert tr.spans()[0]["name"] == "a"  # ring store unaffected


def test_cluster_span_propagation(tmp_path, rng):
    """trace:true search produces a linked span tree across router and
    PS processes, queryable per role."""
    from vearch_tpu.cluster.master import MasterServer
    from vearch_tpu.cluster.ps import PSServer
    from vearch_tpu.cluster.router import RouterServer
    from vearch_tpu.sdk.client import VearchClient

    master = MasterServer()
    master.start()
    ps = PSServer(data_dir=str(tmp_path / "tr"), master_addr=master.addr)
    ps.start()
    router = RouterServer(master_addr=master.addr)
    router.start()
    try:
        cl = VearchClient(router.addr)
        cl.create_database("t")
        cl.create_space("t", {
            "name": "s", "partition_num": 2,
            "fields": [{"name": "v", "data_type": "vector", "dimension": 16,
                        "index": {"index_type": "FLAT", "metric_type": "L2",
                                  "params": {}}}],
        })
        vecs = rng.standard_normal((40, 16)).astype(np.float32)
        cl.upsert("t", "s", [{"_id": f"d{i}", "v": vecs[i]}
                             for i in range(40)])
        import vearch_tpu.cluster.rpc as rpc

        out = rpc.call(router.addr, "POST", "/document/search", {
            "db_name": "t", "space_name": "s",
            "vectors": [{"field": "v", "feature": vecs[3].tolist()}],
            "limit": 3, "trace": True,
        })
        tid = out["trace_id"]
        assert out["params"]  # timing breakdown still present

        _wait_for_serve_span(router.tracer, tid)
        r_spans = _fetch_traces(router.addr, tid)
        names = [s["name"] for s in r_spans]
        assert "router.search" in names
        assert names.count("router.scatter") == 2  # one per partition
        root = next(s for s in r_spans if s["name"] == "router.search")
        for s in r_spans:
            if s["name"] in ("router.scatter", "router.merge"):
                assert s["parent_id"] == root["span_id"]

        p_spans = _fetch_traces(ps.addr, tid)
        searches = [s for s in p_spans if s["name"] == "ps.search"]
        assert len(searches) == 2  # one ps.search per partition
        scatter_ids = {s["span_id"] for s in r_spans
                       if s["name"] == "router.scatter"}
        search_ids = {s["span_id"] for s in searches}
        serves = {s["span_id"]: s for s in p_spans
                  if s["name"] == "rpc.serve"}
        for s in searches:
            assert s["service"] == "ps"
            assert s["trace_id"] == tid
            # joined under the router's scatter spans through the PS's
            # rpc.serve (the scatter span wraps the rpc, and its id
            # rides the envelope)
            assert serves[s["parent_id"]]["parent_id"] in scatter_ids
            # engine phase timings ride as tags, prediction beside them
            assert any(k.endswith("_ms") for k in s["tags"])
            assert s["tags"].get("predicted_dispatches") is not None
        # per-phase engine + kernel child spans under each ps.search
        # (observability tentpole: the search is no longer opaque)
        child_names = {s["name"] for s in p_spans
                       if s["parent_id"] in search_ids}
        assert "ps.gate_wait" in child_names
        assert any(n.startswith("engine.search.") for n in child_names)
        assert any(n.startswith("kernel.") for n in child_names)
        assert {"ps.pre", "ps.post"} <= child_names
        for s in p_spans:
            if s["name"] in ("ps.search", "rpc.decode", "rpc.encode"):
                assert s["parent_id"] in serves
            elif s["name"] != "rpc.serve":
                assert s["parent_id"] in search_ids

        # untraced searches produce no new spans
        before = len(_fetch_traces(router.addr, ""))
        rpc.call(router.addr, "POST", "/document/search", {
            "db_name": "t", "space_name": "s",
            "vectors": [{"field": "v", "feature": vecs[3].tolist()}],
            "limit": 3,
        })
        assert len(_fetch_traces(router.addr, "")) == before
    finally:
        router.stop()
        ps.stop()
        master.stop()


# -- the served search path's tree (profile: true) -----------------------------

D_TREE = 16


@pytest.fixture(scope="module")
def ivfpq_cluster(tmp_path_factory):
    """One router + one PS in this process, one IVFPQ partition, built:
    a search there is served by the fused scan program."""
    import vearch_tpu.cluster.rpc as rpc
    from vearch_tpu.cluster.standalone import StandaloneCluster
    from vearch_tpu.sdk.client import VearchClient

    c = StandaloneCluster(
        data_dir=str(tmp_path_factory.mktemp("tree") / "c"), n_ps=1)
    c.start()
    cl = VearchClient(c.router_addr)
    cl.create_database("db")
    cl.create_space("db", {
        "name": "s", "partition_num": 1,
        "fields": [{"name": "v", "data_type": "vector",
                    "dimension": D_TREE,
                    "index": {"index_type": "IVFPQ", "metric_type": "L2",
                              "params": {"ncentroids": 16, "nsubvector": 8,
                                         "train_iters": 4,
                                         "training_threshold": 256,
                                         "mesh_serving": "off"}}}],
    })
    vecs = np.random.default_rng(5).standard_normal(
        (600, D_TREE)).astype(np.float32)
    cl.upsert("db", "s", [{"_id": f"d{i}", "v": vecs[i]}
                          for i in range(600)])
    ps = c.ps_nodes[0]
    for pid in ps.engines:
        rpc.call(ps.addr, "POST", "/ps/index/build", {"partition_id": pid})
    # warm both paths once, so that first placement (engine.replace_raw)
    # and compilation are behind us
    for prof in (True, False):
        cl.search("db", "s", [{"field": "v", "feature": vecs[:3]}],
                  limit=5, fields=[], profile=prof)
    yield c, cl, vecs
    c.stop()


def _request_spans(trace_id=None):
    """snapshot() without the process-level spans (proc.gc, ps.flush,
    an unrequested engine.replace_raw): they belong to no request."""
    from vearch_tpu.cluster import tracing

    return [r for r in tracing.snapshot()
            if r.parent_id is not None or r.name == "rpc.serve"
            if trace_id is None or r.trace_id == trace_id]


def _profiled_tree(ivfpq_cluster):
    _c, cl, vecs = ivfpq_cluster
    out = cl.search("db", "s", [{"field": "v", "feature": vecs[:3]}],
                    limit=5, fields=[], profile=True)
    assert "params" not in out  # `trace: true` alone decides that
    assert out["profile"]["partition_count"] == 1
    for role in (_c.ps_nodes[0], _c.router):
        _wait_for_serve_span(role.tracer, out["trace_id"], "rpc.encode")
    spans = _request_spans(out["trace_id"])
    return spans, {r.span_id: r for r in spans}


def test_profiled_search_yields_one_tree(ivfpq_cluster):
    spans, by_id = _profiled_tree(ivfpq_cluster)
    roots = [r for r in spans if r.parent_id is None]
    assert [(r.service, r.name) for r in roots] == [("router", "rpc.serve")]
    assert roots[0].tags["bytes_in"] > 0 and roots[0].tags["bytes_out"] > 0
    names = {(r.service, r.name) for r in spans}
    assert names >= {
        ("router", "rpc.decode"), ("router", "rpc.encode"),
        ("router", "router.search"), ("router", "router.scatter"),
        ("router", "router.merge"), ("ps", "rpc.serve"),
        ("ps", "rpc.decode"), ("ps", "rpc.encode"), ("ps", "ps.search"),
        ("ps", "ps.pre"), ("ps", "ps.gate_wait"), ("ps", "ps.post"),
        ("ps", "engine.search.v"), ("ps", "kernel.fused_scan_rerank"),
    }, names
    assert sum(r.name == "ps.pre" for r in spans) == 2  # around the gate
    for r in spans:
        if r.parent_id is None:
            continue
        parent = by_id[r.parent_id]  # every span's parent exists
        # children lie inside their parents on the one clock (the
        # engine's rows come back through microseconds). One pair spans
        # two threads: the PS stamps the end of its rpc.serve after the
        # write, and the router may have the reply a moment earlier.
        assert parent.t0_ns - 1000 <= r.t0_ns <= r.t1_ns, (r.name, parent.name)
        if (r.service, r.name) != ("ps", "rpc.serve"):
            assert r.t1_ns <= parent.t1_ns + 1000, (r.name, parent.name)
    parent_of = {r.name: by_id[r.parent_id].name for r in spans
                 if r.parent_id is not None and r.service == "ps"}
    assert parent_of["rpc.serve"] == "router.scatter"
    assert parent_of["ps.search"] == "rpc.serve"
    assert parent_of["kernel.fused_scan_rerank"] == "ps.search"
    kernels = [r for r in spans if r.name.startswith("kernel.")]
    for r in kernels:
        assert 0 <= r.tags["launch_us"] <= (r.t1_ns - r.t0_ns) // 1000
        assert r.tags["rows"] == 3 and r.tags["bucket_rows"] == 8
    # leaves of the handler threads carry their CPU time
    for r in spans:
        if r.name in ("rpc.decode", "rpc.encode", "ps.pre", "ps.post"):
            assert 0 <= r.cpu_ns <= r.t1_ns - r.t0_ns + 1000, r.name


def test_self_times_of_a_request_add_up_to_the_root(ivfpq_cluster):
    """Self time (duration minus what the children cover, overlaps
    once) over every span of the request is the root's duration: no
    part of the request is counted twice or left out."""
    spans, by_id = _profiled_tree(ivfpq_cluster)
    kids: dict = {}
    for r in spans:
        kids.setdefault(r.parent_id, []).append(r)

    def self_ns(r):
        covered, edge = 0, r.t0_ns
        for c in sorted(kids.get(r.span_id, []), key=lambda c: c.t0_ns):
            lo, hi = max(c.t0_ns, edge), min(c.t1_ns, r.t1_ns)
            if hi > lo:
                covered += hi - lo
                edge = hi
        return (r.t1_ns - r.t0_ns) - covered

    root = kids[None][0]
    total = sum(self_ns(r) for r in spans)
    # what of the PS's rpc.serve trails its router.scatter (see above)
    # is the PS's own and under no parent
    ps_serve = next(r for r in spans
                    if (r.service, r.name) == ("ps", "rpc.serve"))
    total -= max(0, ps_serve.t1_ns - by_id[ps_serve.parent_id].t1_ns)
    # siblings that overlap (kernel.* inside engine.search.*) are
    # covered once in the parent and counted in full themselves
    overlap = sum(
        max(0, min(a.t1_ns, b.t1_ns) - max(a.t0_ns, b.t0_ns))
        for sibs in kids.values()
        for i, a in enumerate(sibs) for b in sibs[i + 1:])
    assert abs(total - overlap - (root.t1_ns - root.t0_ns)) <= 20_000


def test_unsampled_search_adds_no_span(ivfpq_cluster):
    _c, cl, vecs = ivfpq_cluster
    before = len(_request_spans())
    docs = cl.search("db", "s", [{"field": "v", "feature": vecs[:3]}],
                     limit=5, fields=[])
    assert len(docs) == 3
    assert len(_request_spans()) == before


def test_write_then_search_shows_replace_raw_under_the_request(tmp_path):
    """The raw store's first placement is paid by the first search: a
    profiled one carries it as a child of its ps.search."""
    from vearch_tpu.cluster.standalone import StandaloneCluster
    from vearch_tpu.sdk.client import VearchClient

    c = StandaloneCluster(data_dir=str(tmp_path / "c"), n_ps=1).start()
    try:
        cl = VearchClient(c.router_addr)
        cl.create_database("db")
        cl.create_space("db", {
            "name": "s", "partition_num": 1,
            "fields": [{"name": "v", "data_type": "vector", "dimension": 8,
                        "index": {"index_type": "FLAT",
                                  "metric_type": "L2", "params": {}}}],
        })
        vecs = np.random.default_rng(6).standard_normal(
            (40, 8)).astype(np.float32)
        cl.upsert("db", "s", [{"_id": f"d{i}", "v": vecs[i]}
                              for i in range(40)])
        out = cl.search("db", "s", [{"field": "v", "feature": vecs[:1]}],
                        limit=3, profile=True)
        _wait_for_serve_span(c.router.tracer, out["trace_id"])
        spans = _request_spans(out["trace_id"])
        by_id = {r.span_id: r for r in spans}
        placed = [r for r in spans if r.name == "engine.replace_raw"]
        assert len(placed) == 1 and placed[0].tags["bytes"] > 0
        assert by_id[placed[0].parent_id].name == "ps.search"
    finally:
        c.stop()


def test_named_scopes_reach_the_hlo_metadata():
    """The four stages of the fused scan program carry their scope in
    every operation's op_name: what the profiler's viewer groups by."""
    import re

    import jax.numpy as jnp

    from vearch_tpu.ops import ivf

    n, d, b = 512 * 160, 16, 8  # enough blocks for block-max selection
    lowered = ivf.int8_scan_rerank.lower(
        jnp.zeros((b, d), jnp.float32), jnp.zeros((n, d), jnp.int8),
        jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
        jnp.ones((n,), bool), jnp.zeros((n, d), jnp.float32),
        jnp.ones((n,), jnp.float32), 32, 10)
    op_names = set(re.findall(r'op_name="([^"]+)"',
                              lowered.compile().as_text()))
    for scope in ("score", "block_max", "select", "rerank"):
        assert any(re.search(rf"(^|/){scope}/", name) for name in op_names), (
            scope, sorted(op_names)[:8])
