"""Document-op edge cases mirroring the reference suite (reference:
test/test_document_upsert.py update() with has_vector=False —
partial updates; test_document_search.py
test_vearch_document_search_with_score_filter — min/max score windows;
badcase classes — validation)."""

import numpy as np
import pytest

from vearch_tpu.cluster.rpc import RpcError
from vearch_tpu.cluster.standalone import StandaloneCluster
from vearch_tpu.sdk.client import VearchClient
import vearch_tpu.cluster.rpc as rpc

D = 8


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    with StandaloneCluster(
        data_dir=str(tmp_path_factory.mktemp("edges")), n_ps=2
    ) as c:
        yield c


@pytest.fixture(scope="module")
def client(cluster):
    cl = VearchClient(cluster.router_addr)
    cl.create_database("db")
    cl.create_space("db", {
        "name": "sp", "partition_num": 2, "replica_num": 1,
        "fields": [
            {"name": "color", "data_type": "string"},
            {"name": "price", "data_type": "float"},
            {"name": "emb", "data_type": "vector", "dimension": D,
             "index": {"index_type": "FLAT", "metric_type": "L2",
                       "params": {}}},
        ],
    })
    return cl


@pytest.fixture(scope="module")
def vecs(client):
    rng = np.random.default_rng(5)
    v = rng.standard_normal((40, D)).astype(np.float32)
    client.upsert("db", "sp", [
        {"_id": f"d{i}", "color": "red", "price": float(i), "emb": v[i]}
        for i in range(40)
    ])
    return v


def _get(client, _id):
    docs = client.query("db", "sp", document_ids=[_id])
    return docs[0] if docs else None


def test_partial_update_without_vector(client, vecs):
    """Upsert with only scalars for an existing _id: scalars change, the
    stored vector survives (reference: add(has_vector=False))."""
    client.upsert("db", "sp", [{"_id": "d3", "color": "blue"}])
    doc = _get(client, "d3")
    assert doc["color"] == "blue"
    assert doc["price"] == 3.0  # omitted scalar carried forward
    # the vector is the ORIGINAL one: exact search still lands on d3
    hits = client.search("db", "sp",
                         [{"field": "emb", "feature": vecs[3].tolist()}],
                         limit=1)
    assert hits[0][0]["_id"] == "d3"


def test_partial_update_new_id_is_rejected(client):
    with pytest.raises(RpcError) as e:
        client.upsert("db", "sp", [{"_id": "ghost", "color": "x"}])
    assert e.value.code == 400
    assert _get(client, "ghost") is None


def test_partial_update_vector_only(client, vecs):
    """Upsert with only the vector: scalars carry forward."""
    newv = np.full(D, 9.0, dtype=np.float32)
    client.upsert("db", "sp", [{"_id": "d5", "emb": newv}])
    doc = _get(client, "d5")
    assert doc["color"] == "red" and doc["price"] == 5.0
    hits = client.search("db", "sp",
                         [{"field": "emb", "feature": newv.tolist()}],
                         limit=1)
    assert hits[0][0]["_id"] == "d5"


def test_search_score_window(client, vecs):
    """min_score/max_score bound the user-facing score (L2: distance²,
    lower = closer). max_score=0.01 keeps only the self-match."""
    out = rpc.call(client.addr, "POST", "/document/search", {
        "db_name": "db", "space_name": "sp",
        "vectors": [{"field": "emb", "feature": vecs[7].tolist(),
                     "max_score": 0.01}],
        "limit": 10,
    })
    rows = out["documents"][0]
    assert [r["_id"] for r in rows] == ["d7"]
    assert rows[0]["_score"] <= 0.01

    # a min_score floor excludes the self-match but keeps neighbors
    out = rpc.call(client.addr, "POST", "/document/search", {
        "db_name": "db", "space_name": "sp",
        "vectors": [{"field": "emb", "feature": vecs[7].tolist(),
                     "min_score": 0.01}],
        "limit": 10,
    })
    rows = out["documents"][0]
    assert rows and all(r["_id"] != "d7" for r in rows)
    assert all(r["_score"] >= 0.01 for r in rows)


def test_upsert_validation_badcases(client):
    # wrong vector length
    with pytest.raises(RpcError) as e:
        client.upsert("db", "sp", [
            {"_id": "bad", "color": "x", "price": 0.0,
             "emb": [0.0] * (D + 1)}])
    assert e.value.code == 400
    # unknown field
    with pytest.raises(RpcError) as e:
        client.upsert("db", "sp", [
            {"_id": "bad", "nope": 1, "emb": [0.0] * D}])
    assert e.value.code == 400
    # a failed batch must not write anything
    assert _get(client, "bad") is None


def test_null_vector_means_keep_stored(client, vecs):
    """A JSON null vector is the natural 'keep the stored one' idiom —
    it must behave exactly like omitting the field."""
    client.upsert("db", "sp", [{"_id": "d11", "emb": None,
                                "color": "violet"}])
    doc = _get(client, "d11")
    assert doc["color"] == "violet"
    hits = client.search("db", "sp",
                         [{"field": "emb", "feature": vecs[11].tolist()}],
                         limit=1)
    assert hits[0][0]["_id"] == "d11"  # stored vector intact


def test_same_id_twice_in_one_batch(client, vecs):
    """[full update, partial update] of the same _id in ONE batch: the
    partial inherits the NEW vector from earlier in the batch, not the
    pre-batch one (reviewer-found ordering bug)."""
    newv = np.full(D, -7.0, dtype=np.float32)
    client.upsert("db", "sp", [
        {"_id": "d13", "color": "gold", "price": 1.0, "emb": newv},
        {"_id": "d13", "color": "silver"},
    ])
    doc = _get(client, "d13")
    assert doc["color"] == "silver" and doc["price"] == 1.0
    hits = client.search("db", "sp",
                         [{"field": "emb", "feature": newv.tolist()}],
                         limit=1)
    assert hits[0][0]["_id"] == "d13"  # batch-internal vector won


def test_bad_scalar_type_rejected_without_corruption(client, vecs):
    """A scalar value the column cannot take must 400 BEFORE any
    mutation — a mid-batch failure would desync table rows from vector
    rows forever (reviewer-found invariant hazard)."""
    with pytest.raises(RpcError) as e:
        client.upsert("db", "sp", [
            {"_id": "t1", "color": "x", "price": "not-a-number",
             "emb": np.zeros(D, dtype=np.float32)}])
    assert e.value.code == 400
    assert _get(client, "t1") is None
    # the engine still works and rows still line up
    client.upsert("db", "sp", [
        {"_id": "t2", "color": "y", "price": 2.0,
         "emb": np.full(D, 4.0, dtype=np.float32)}])
    hits = client.search(
        "db", "sp",
        [{"field": "emb", "feature": np.full(D, 4.0, np.float32)}],
        limit=1)
    assert hits[0][0]["_id"] == "t2"


def test_partial_update_does_not_index_phantom_defaults(tmp_path):
    """A doc that never set an INT field stores the column default (0);
    a partial update must not resurrect that 0 as a filterable value
    (reviewer-found phantom-index bug)."""
    from vearch_tpu.engine.engine import Engine
    from vearch_tpu.engine.types import (
        DataType, FieldSchema, IndexParams, MetricType, ScalarIndexType,
        TableSchema,
    )

    schema = TableSchema("t", [
        FieldSchema("price", DataType.INT,
                    scalar_index=ScalarIndexType.INVERTED),
        FieldSchema("tag", DataType.STRING),
        FieldSchema("v", DataType.VECTOR, dimension=4,
                    index=IndexParams("FLAT", MetricType.L2, {})),
    ])
    eng = Engine(schema)
    eng.upsert([
        {"_id": "a", "v": [0.0] * 4},               # price never set
        {"_id": "b", "price": 0, "v": [1.0] * 4},   # price really 0
    ])
    eng.upsert([{"_id": "a", "tag": "x"}])  # partial update of a
    docs = eng.query({"operator": "AND", "conditions": [
        {"operator": "=", "field": "price", "value": 0}]}, limit=10)
    assert [d["_id"] for d in docs] == ["b"], docs


def test_partial_update_on_disk_store(tmp_path):
    """Vector inheritance reads the stored row off the mmap disk tier
    (DiskRawVectorStore.get) — partial updates must work for
    store_type: RocksDB/Disk spaces too."""
    from vearch_tpu.engine.disk_vector import DiskRawVectorStore
    from vearch_tpu.engine.engine import Engine, SearchRequest
    from vearch_tpu.engine.types import (
        DataType, FieldSchema, IndexParams, MetricType, TableSchema,
    )

    schema = TableSchema("t", [
        FieldSchema("tag", DataType.STRING),
        FieldSchema("v", DataType.VECTOR, dimension=16,
                    index=IndexParams("FLAT", MetricType.L2,
                                      {"store_type": "RocksDB"})),
    ])
    eng = Engine(schema, data_dir=str(tmp_path / "d"))
    assert isinstance(eng.vector_stores["v"], DiskRawVectorStore)
    rng = np.random.default_rng(21)
    vecs = rng.standard_normal((30, 16)).astype(np.float32)
    eng.upsert([{"_id": f"k{i}", "tag": "a", "v": vecs[i]}
                for i in range(30)])
    eng.upsert([{"_id": "k4", "tag": "b"}])  # scalars only
    res = eng.search(SearchRequest(vectors={"v": vecs[4:5]}, k=1))
    assert res[0].items[0].key == "k4"
    assert res[0].items[0].fields["tag"] == "b"


def test_microbatch_score_bounds_not_shared():
    """Concurrent bounded and unbounded searches must not co-batch into
    one request that drops the window (reviewer-found silent-wrong-
    results bug)."""
    import threading

    from vearch_tpu.engine.engine import Engine, SearchRequest
    from vearch_tpu.engine.types import (
        DataType, FieldSchema, IndexParams, MetricType, TableSchema,
    )

    schema = TableSchema("t", [
        FieldSchema("v", DataType.VECTOR, dimension=4,
                    index=IndexParams("FLAT", MetricType.L2, {})),
    ])
    eng = Engine(schema)
    rng = np.random.default_rng(0)
    base = rng.standard_normal((50, 4)).astype(np.float32)
    eng.upsert([{"_id": str(i), "v": base[i]} for i in range(50)])

    q = base[7:8]
    out: dict[str, list] = {}

    def run(name, bounds):
        out[name] = eng.search(SearchRequest(
            vectors={"v": q}, k=10, include_fields=[],
            score_bounds=bounds))

    ts = [
        threading.Thread(target=run, args=("bounded",
                                           {"v": (None, 1e-4)})),
        threading.Thread(target=run, args=("free", None)),
    ]
    [t.start() for t in ts]
    [t.join() for t in ts]
    bounded = out["bounded"][0].items
    free = out["free"][0].items
    assert [it.key for it in bounded] == ["7"]  # window enforced
    assert len(free) == 10  # unbounded untouched


def test_full_update_still_replaces_everything(client, vecs):
    client.upsert("db", "sp", [
        {"_id": "d9", "color": "green", "price": 99.0,
         "emb": np.ones(D, dtype=np.float32)}])
    doc = _get(client, "d9")
    assert doc["color"] == "green" and doc["price"] == 99.0
    hits = client.search(
        "db", "sp",
        [{"field": "emb", "feature": np.ones(D, dtype=np.float32)}],
        limit=1)
    assert hits[0][0]["_id"] == "d9"


def test_columnar_response_matches_json(client, vecs):
    """A fields-free search, which the SDK asks in the array form,
    returns exactly the JSON rows' results (ids AND scores)."""
    q = [{"field": "emb", "feature": vecs[:8]}]
    plain = client.search("db", "sp", q, limit=5)
    col = client.search("db", "sp", q, limit=5, fields=[])
    assert len(col) == 8
    for a, b in zip(plain, col):
        assert [r["_id"] for r in a] == [r["_id"] for r in b]
        for ra, rb in zip(a, b):
            assert abs(ra["_score"] - rb["_score"]) < 1e-4
