"""profile:true explain surface (observability tentpole acceptance).

A profiled search returns a router-merged, per-partition, per-phase
timing + dispatch breakdown (the Elasticsearch `profile`/SQL EXPLAIN
analogue), with MEASURED dispatch tags asserted equal to the perf
model's DOCUMENTED_DISPATCHES for the active path — the same gate
test_perf_gates.py applies via the ledger, now visible per request on
the public API.
"""

import numpy as np
import pytest

import vearch_tpu.cluster.rpc as rpc
from vearch_tpu.cluster.standalone import StandaloneCluster
from vearch_tpu.engine.engine import SearchRequest
from vearch_tpu.ops import perf_model
from vearch_tpu.sdk.client import VearchClient

from tests.test_perf_gates import IVFPQ_PARAMS, _build

D = 16


@pytest.fixture()
def cluster(tmp_path):
    c = StandaloneCluster(data_dir=str(tmp_path / "p"), n_ps=2)
    c.start()
    yield c
    c.stop()


def test_profile_multi_partition_router_merge(cluster, rng):
    """The acceptance gate: profile:true on a 2-partition search comes
    back with one breakdown per partition, each carrying phase timings
    and dispatch tags equal to DOCUMENTED_DISPATCHES for its path."""
    cl = VearchClient(cluster.router_addr)
    cl.create_database("db")
    cl.create_space("db", {
        "name": "s", "partition_num": 2,
        "fields": [{"name": "v", "data_type": "vector", "dimension": D,
                    "index": {"index_type": "FLAT", "metric_type": "L2",
                              "params": {}}}],
    })
    vecs = rng.standard_normal((60, D)).astype(np.float32)
    cl.upsert("db", "s", [{"_id": f"d{i}", "v": vecs[i]}
                          for i in range(60)])

    out = cl.search("db", "s", [{"field": "v", "feature": vecs[7]}],
                    limit=3, profile=True)
    # profiled responses keep the documents — profiling is additive
    assert out["documents"][0][0]["_id"] == "d7"

    prof = out["profile"]
    assert prof["partition_count"] == 2
    assert len(prof["partitions"]) == 2
    assert prof["merge_ms"] >= 0
    for pid, part in prof["partitions"].items():
        assert part["rpc_ms"] > 0
        phases = part["phases"]
        # engine + PS phases all present per partition
        for phase in ("gate_wait", "queue", "filter", "merge", "shape",
                      "total"):
            assert phase in phases, (pid, phases)
        assert any(p.startswith("search_") for p in phases)
        assert part["doc_count"] > 0  # this partition's share
        # measured dispatches == documented dispatches for the path
        disp = part["dispatches"]
        assert disp["path"] == "flat"
        assert disp["tags"] == perf_model.DOCUMENTED_DISPATCHES["flat"]
        assert disp["predicted"] == disp["tags"]
        assert disp["count"] == 1
        assert disp["predicted_scan_bytes"] > 0
        assert set(disp["per_dispatch_ms"]) == set(disp["tags"])
        assert all(v >= 0 for v in disp["per_dispatch_ms"].values())
    # the partitions jointly hold the whole corpus
    assert sum(p["doc_count"] for p in prof["partitions"].values()) == 60

    # unprofiled searches carry no profile payload (and no trace cost)
    plain = rpc.call(cluster.router_addr, "POST", "/document/search", {
        "db_name": "db", "space_name": "s",
        "vectors": [{"field": "v", "feature": vecs[7].tolist()}],
        "limit": 3,
    })
    assert "profile" not in plain


def test_profile_dispatches_match_documented_per_ivfpq_path(tmp_path):
    """Engine-level: every IVFPQ serving path's profiled trace reports
    exactly its documented dispatch sequence, with the perf model's
    reverse lookup naming the path and a byte prediction beside it."""
    eng, vecs = _build("IVFPQ", IVFPQ_PARAMS, warmup=[8])
    # a disk store takes the two-step full scan
    disk_eng, _ = _build(
        "IVFPQ", {**IVFPQ_PARAMS, "store_type": "RocksDB"}, n=1000,
        data_dir=str(tmp_path))
    doc = perf_model.DOCUMENTED_DISPATCHES
    cases = {
        "ivfpq_full_fused": (eng, {"scan_mode": "full"}),
        "ivfpq_full_unfused": (disk_eng, {"scan_mode": "full"}),
        "ivfpq_probe": (eng, {"scan_mode": "probe"}),
    }
    for path, (engine, params) in cases.items():
        trace: dict = {}
        engine.search(SearchRequest(
            vectors={"emb": vecs[:8]}, k=10, include_fields=[],
            index_params=params, trace=trace))
        assert trace["dispatches"] == doc[path], path
        assert trace["perf_path"] == path
        assert trace["predicted_dispatches"] == doc[path]
        assert trace["dispatch_count"] == len(doc[path])
        assert trace["predicted_scan_bytes"] > 0
        for tag in doc[path]:
            assert trace[f"dispatch_{tag}_ms"] >= 0
        # kernel wall windows ride as phase spans next to engine phases
        span_names = [s[0] for s in trace["_phase_spans"]]
        for tag in doc[path]:
            assert f"kernel.{tag}" in span_names
        assert "engine.search.emb" in span_names


def test_path_for_dispatches_reverse_lookup():
    doc = perf_model.DOCUMENTED_DISPATCHES
    for path, tags in doc.items():
        assert perf_model.path_for_dispatches(list(tags)) == path
    assert perf_model.path_for_dispatches(["nope"]) is None
    # the empty sequence is now a *documented* path: a cache hit
    # launches zero device programs by design
    assert perf_model.path_for_dispatches([]) == "cache_hit"


def test_profile_disabled_trace_has_no_capture(cluster, rng):
    """trace:true alone still gets timing tags (existing behavior) but
    the response body carries no profile block — profile is opt-in."""
    cl = VearchClient(cluster.router_addr)
    cl.create_database("t2")
    cl.create_space("t2", {
        "name": "s", "partition_num": 1,
        "fields": [{"name": "v", "data_type": "vector", "dimension": D,
                    "index": {"index_type": "FLAT", "metric_type": "L2",
                              "params": {}}}],
    })
    vecs = rng.standard_normal((20, D)).astype(np.float32)
    cl.upsert("t2", "s", [{"_id": f"d{i}", "v": vecs[i]}
                          for i in range(20)])
    out = rpc.call(cluster.router_addr, "POST", "/document/search", {
        "db_name": "t2", "space_name": "s",
        "vectors": [{"field": "v", "feature": vecs[3].tolist()}],
        "limit": 3, "trace": True,
    })
    assert out["trace_id"]
    assert "profile" not in out
