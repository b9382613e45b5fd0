"""The array form of a fields-free search reply (cluster/hitarrays.py):
codec round trip, `_encode`'s shallow pass against the full walk, the
router's array merge against the row merge, version skew, and what the
SDK hands back, through a live cluster."""

import json
import time

import numpy as np
import pytest

import vearch_tpu.cluster.rpc as rpc
from vearch_tpu.cluster import hitarrays, tracing
from vearch_tpu.cluster.router import RouterServer
from vearch_tpu.cluster.standalone import StandaloneCluster
from vearch_tpu.sdk.client import VearchClient

from tests.test_metrics_gauges import scrape

D = 8


# -- the codec ----------------------------------------------------------------

def _encode_full_walk(body):
    """`rpc._encode` as it was before the shallow pass: every node of the
    body visited in Python. The reference of the frame's bytes."""
    try:
        return rpc.JSON_CT, json.dumps(body).encode()
    except TypeError:
        pass
    tensors, paths = [], []
    skeleton = rpc._extract_tensors(body, tensors, paths, ())
    arrays = [np.ascontiguousarray(t) for t in tensors]
    header = json.dumps({
        "body": skeleton, "paths": paths,
        "tensors": [{"dtype": a.dtype.str, "shape": list(a.shape)}
                    for a in arrays],
    }).encode()
    return rpc.BIN_CT, b"".join(
        [rpc._U32.pack(len(header)), header] + [a.tobytes() for a in arrays])


_KEY_CASES = {
    "ascii": (["d1", "d22", "d333", "d4"], [2, 2]),
    "unicode": (["clé", "日本語のキー", "a", "ключ-7", "😀x"], [3, 2]),
    "unequal_lengths": (["a", "bb" * 40, "", "c" * 7], [1, 3]),
    "a_query_with_no_hit": (["x", "y", "z"], [2, 0, 1, 0]),
    "fewer_than_k": (["h0", "h1", "h2", "h3", "h4", "h5"], [5, 1]),
    "no_hit_at_all": ([], [0, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(_KEY_CASES))
def test_arrays_reply_round_trips_through_the_codec(case):
    keys, counts = _KEY_CASES[case]
    scores = (np.arange(len(keys), dtype=np.float32) / 3).astype(np.float32)
    reply = {"metric": "L2", **hitarrays.pack(keys, counts, scores),
             "apply_version": 7, "map_version": 1}
    ct, data = rpc._encode({"code": 0, "data": reply})
    assert ct == rpc.BIN_CT
    header = json.loads(data[4:4 + rpc._U32.unpack_from(data, 0)[0]])
    # the frame's JSON part holds scalars only: no key, no score
    assert header["paths"] == [["data", name] for name in hitarrays.ARRAYS]
    assert all(v is None or not isinstance(v, (list, dict))
               for v in header["body"]["data"].values())
    back = rpc._decode(ct, data)["data"]
    assert hitarrays.is_arrays(back)
    assert back["key_blob"].dtype == np.uint8
    assert back["key_lens"].dtype == back["counts"].dtype == np.int32
    assert back["counts"].tolist() == counts
    assert hitarrays.keys_of(back) == keys
    rows = hitarrays.to_rows(back)
    assert [len(r) for r in rows] == counts
    assert [h["_id"] for r in rows for h in r] == keys
    # a float32 score comes out as the Python float its JSON repr
    # parses back to: what a `documents` reply carries
    flat = [h["_score"] for r in rows for h in r]
    assert flat == json.loads(json.dumps(scores.tolist()))
    assert all(type(s) is float for s in flat)
    assert hitarrays.to_rows(hitarrays.from_rows(rows)) == rows


def _bodies():
    rng = np.random.default_rng(3)
    vec = lambda n=D: rng.standard_normal(n).astype(np.float32)  # noqa: E731
    docs = [{"_id": f"d{i}", "color": "red", "price": float(i),
             "emb": vec()} for i in range(5)]
    return {
        "sdk_upsert": {"db_name": "db", "space_name": "sp",
                       "documents": docs},
        "ps_write": {"partition_id": 3, "documents": docs, "profile": False},
        "query_request": {"db_name": "db", "space_name": "sp",
                          "document_ids": ["d1", "d2"], "limit": 50,
                          "vector_value": True},
        "query_reply": {"code": 0, "data": {"documents": [
            {"_id": "d1", "price": 1.0, "emb": vec().tolist()}]}},
        "raft_append": {
            "pid": 3, "term": 2, "leader": 1, "prev_index": 9,
            "prev_term": 2, "commit": 9,
            "entries": [{"index": 10 + i, "term": 2,
                         "op": {"type": "upsert", "docs": docs[i:i + 2]}}
                        for i in range(3)]},
        "backup": {"command": "restore", "version": 4, "async": True,
                   "store": {"type": "local", "root": "/x"}},
        "sdk_search": {"db_name": "db", "space_name": "sp", "limit": 10,
                       "vectors": [{"field": "emb", "feature": vec(64 * D)}],
                       "fields": [], "columnar": True},
        "ps_search": {"vectors": {"emb": vec(64 * D).reshape(64, D)},
                      "k": 10, "include_fields": [], "columnar_wire": True,
                      "index_params": {}, "partition_id": 3,
                      "_trace_ctx": {"trace_id": "t", "parent": "p"}},
        "key_lists_reply": {"code": 0, "data": {
            "metric": "L2", "columnar": True,
            "keys": [[f"k{q}_{j}" for j in range(10)] for q in range(64)],
            "scores": vec(640), "apply_version": 1}},
        "shallow_and_deep": {"a": vec(), "b": {"c": vec(), "d": [vec()]}},
        "deep_first": {"a": {"b": {"c": vec()}}, "z": vec()},
        "int_keys": {1: vec(), "m": {2: vec(), "t": (1, 2)}},
        "bare_array": vec(),
        "list_body": [1, {"v": vec()}],
    }


@pytest.mark.parametrize("name", sorted(_bodies()))
def test_encode_frames_byte_for_byte_what_the_full_walk_framed(name):
    body = _bodies()[name]
    ct, data = rpc._encode(body)
    assert (ct, data) == _encode_full_walk(body)


@pytest.mark.parametrize("shape", [(0,), (5,), (2, 3), (2, 0, 3), (1, 1, 4)])
def test_decode_restores_every_shape(shape):
    """The restore takes a flat view and reshapes only what is not flat."""
    a = np.arange(int(np.prod(shape)), dtype=np.float32).reshape(shape)
    b = np.arange(3, dtype=np.int32)
    back = rpc._decode(*rpc._encode({"data": {"a": a, "b": b, "n": 1}}))
    assert back["data"]["a"].shape == shape and back["data"]["n"] == 1
    np.testing.assert_array_equal(back["data"]["a"], a)
    np.testing.assert_array_equal(back["data"]["b"], b)


def test_encode_does_not_visit_what_holds_no_array(monkeypatch):
    """A reply whose arrays sit at `data.<name>` is framed without the
    recursive walk, however many nodes the rest of it holds."""
    calls = []
    real = rpc._extract_tensors
    monkeypatch.setattr(
        rpc, "_extract_tensors",
        lambda *a: calls.append(1) or real(*a))
    body = _bodies()["key_lists_reply"]
    rpc._encode(body)
    rpc._encode({"code": 0, "data": {
        **hitarrays.pack(["a", "b"], [2], [0.1, 0.2]),
        "profile": {"partitions": {"1": {"phases": {"x": 1.0}}}}}})
    assert calls == []
    rpc._encode(_bodies()["sdk_upsert"])  # arrays under a list: the walk
    assert calls


# -- the router's merge -------------------------------------------------------

def _partials(rng, metric, n_parts, nq, k, ragged, unicode_keys=False):
    """Array-form partials and the same hits as row-form partials, scores
    drawn from 7 values so that ties abound."""
    arrays, rows = [], []
    for part in range(n_parts):
        counts = (rng.integers(0, k + 1, nq) if ragged
                  else np.full(nq, k)).tolist()
        keys = [[f"p{part}{'é' * (j % 3) if unicode_keys else ''}q{qi}h{j}"
                 for j in range(n)] for qi, n in enumerate(counts)]
        scores = rng.integers(0, 7, sum(counts)).astype(np.float32)
        arrays.append({"metric": metric, **hitarrays.pack(
            [key for ks in keys for key in ks], counts, scores),
            "apply_version": 1})
        flat, lo, res = scores.tolist(), 0, []
        for ks in keys:
            res.append([{"_id": key, "_score": s}
                        for key, s in zip(ks, flat[lo:lo + len(ks)])])
            lo += len(ks)
        rows.append({"metric": metric, "results": res})
    return arrays, rows


@pytest.mark.parametrize("metric", ["L2", "cosine"])
@pytest.mark.parametrize("n_parts", [1, 3])
@pytest.mark.parametrize("ragged", [False, True])
@pytest.mark.parametrize("window", [(0, 10), (0, 3), (4, 3), (8, 5), (10, 4)])
def test_array_merge_is_the_row_merge(metric, n_parts, ragged, window):
    """Same hits, same scores, same order as the dict-row merge, ties
    included (partition order, then the partition's own), every page of
    the top k, rows with fewer hits than k keeping their own count."""
    start, size = window
    rng = np.random.default_rng(n_parts + 10 * ragged)
    nq, k = 64, 10
    arrays, rows = _partials(rng, metric, n_parts, nq, k, ragged,
                             unicode_keys=ragged)
    router = object.__new__(RouterServer)
    want = [r[start:start + size]
            for r in RouterServer._merge_rows(router, rows, k)]
    merged = RouterServer._merge_arrays(arrays, k, start, size)
    assert merged["counts"].tolist() == [len(r) for r in want]
    assert merged["counts"].dtype == merged["key_lens"].dtype == np.int32
    assert merged["scores"].dtype == np.float32
    assert merged["key_blob"].dtype == np.uint8
    assert hitarrays.to_rows(merged) == want
    # and through the frame, as the client gets it
    back = rpc._decode(*rpc._encode({"code": 0, "data": merged}))["data"]
    assert hitarrays.to_rows(back) == want
    if (start, size) == (0, k):
        assert RouterServer._merge_search(router, arrays, k) == want


@pytest.mark.parametrize("metric", ["L2", "cosine"])
@pytest.mark.parametrize("window", [(0, 10), (0, 4), (3, 4)])
def test_one_sorted_partial_is_forwarded_not_gathered(metric, window):
    """What a one-partition space answers: the partition's hits, already
    in merge order. A window that keeps them all forwards the partial's
    own arrays; a narrower one takes the gather. Either way the rows are
    the row merge's."""
    start, size = window
    rng = np.random.default_rng(size)
    arrays, rows = _partials(rng, metric, 1, 32, 10, ragged=True)
    sign = 1 if metric == "L2" else -1
    for res in rows[0]["results"]:
        res.sort(key=lambda h: sign * h["_score"])
    arrays = [{"metric": metric, **hitarrays.from_rows(rows[0]["results"])}]
    router = object.__new__(RouterServer)
    want = [r[start:start + size]
            for r in RouterServer._merge_rows(router, rows, 10)]
    merged = RouterServer._merge_arrays(arrays, 10, start, size)
    assert hitarrays.to_rows(merged) == want
    assert (merged["key_blob"] is arrays[0]["key_blob"]) == (size == 10)


_SKEW = {
    "arrays+key_lists": ("a", "k"),
    "key_lists_only": ("k", "k"),
    "arrays+rows": ("a", "r"),
    "key_lists+arrays+rows": ("k", "a", "r"),
    "rows_only": ("r", "r"),
}


@pytest.mark.parametrize("mix", sorted(_SKEW))
@pytest.mark.parametrize("metric", ["L2", "InnerProduct"])
def test_version_skewed_partials_merge_to_the_same_rows(mix, metric):
    """Partition servers of three ages (arrays, key lists beside flat
    scores, rows) in one fan-out: the merge is that of rows alone."""
    kinds = _SKEW[mix]
    rng = np.random.default_rng(len(mix))
    nq, k = 5, 4
    arrays, rows = _partials(rng, metric, len(kinds), nq, k, ragged=True)
    partials = []
    for kind, a, r in zip(kinds, arrays, rows):
        if kind == "a":
            partials.append(a)
        elif kind == "r":
            partials.append(r)
        else:
            partials.append({
                "metric": metric, "columnar": True,
                "keys": [[h["_id"] for h in row] for row in r["results"]],
                "scores": a["scores"], "apply_version": 1})
    router = object.__new__(RouterServer)
    want = RouterServer._merge_rows(router, rows, k)
    assert RouterServer._merge_search(router, partials, k) == want
    merged = RouterServer._merge_arrays(partials, k, 0, k)
    assert (merged is None) == ("r" in kinds)
    for p in partials:
        if p.get("columnar"):
            back = RouterServer._rows_from_columnar(p)
            assert "columnar" not in back and "key_blob" not in back
            assert back["apply_version"] == 1
            assert back["results"] == rows[partials.index(p)]["results"]


# -- through a live cluster ---------------------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    with StandaloneCluster(
        data_dir=str(tmp_path_factory.mktemp("arrays")), n_ps=2
    ) as c:
        yield c


@pytest.fixture(scope="module")
def client(cluster):
    cl = VearchClient(cluster.router_addr)
    cl.create_database("db")
    for name, metric in (("sp", "L2"), ("cos", "InnerProduct")):
        cl.create_space("db", {
            "name": name, "partition_num": 2, "replica_num": 1,
            "fields": [
                {"name": "price", "data_type": "float"},
                {"name": "emb", "data_type": "vector", "dimension": D,
                 "index": {"index_type": "FLAT", "metric_type": metric,
                           "params": {}}},
            ],
        })
    return cl


@pytest.fixture(scope="module")
def vecs(client):
    rng = np.random.default_rng(5)
    v = rng.standard_normal((60, D)).astype(np.float32)
    v[50:] = v[:10]  # twins: tied scores across and inside partitions
    for space in ("sp", "cos"):
        client.upsert("db", space, [
            {"_id": f"{'clé' if i % 7 == 0 else 'd'}{i}", "price": float(i),
             "emb": v[i]} for i in range(60)])
    return v


def _raw_search(cluster, space, q, **extra):
    """The request a caller without the array form sends: no
    `columnar`, so the router answers `documents`."""
    return rpc.call(cluster.router_addr, "POST", "/document/search", {
        "db_name": "db", "space_name": space,
        "vectors": [{"field": "emb", "feature": q.ravel()}],
        "fields": [], **extra})


@pytest.mark.parametrize("space", ["sp", "cos"])
@pytest.mark.parametrize("rows", [1, 40])  # the scheduler's path; the raw path
@pytest.mark.parametrize("profile", [False, True])
def test_sdk_returns_what_the_documents_form_returns(
        cluster, client, vecs, space, rows, profile):
    """Same objects, ids in order and scores to the bit, with and
    without `profile`, from the engine's columnar results (>= 32 rows)
    and from a co-batched dispatch's items (below)."""
    q = [{"field": "emb", "feature": vecs[:rows]}]
    want = _raw_search(cluster, space, vecs[:rows], limit=7,
                       cache=False)["documents"]
    assert len(want) == rows and all(len(r) == 7 for r in want)
    out = client.search("db", space, q, limit=7, fields=[], profile=profile,
                        cache=False)
    if profile:
        assert set(out) >= {"documents", "profile", "trace_id"}
        assert not set(out) & {"columnar", "keys", *hitarrays.ARRAYS}
        assert out["profile"]["partition_count"] == 2
        out = out["documents"]
    assert out == want
    assert all(type(h["_score"]) is float and type(h["_id"]) is str
               for r in out for h in r)


def test_sdk_pages_and_short_rows_keep_their_counts(cluster, client, vecs):
    q = [{"field": "emb", "feature": vecs[:3]}]
    want = _raw_search(cluster, "sp", vecs[:3], limit=10, page_size=4,
                       page_num=2)["documents"]
    assert [len(r) for r in want] == [4, 4, 4]
    assert client.search("db", "sp", q, limit=10, fields=[], page_size=4,
                         page_num=2) == want
    few = {"operator": "AND", "conditions": [
        {"field": "price", "operator": "<", "value": 3}]}
    out = client.search("db", "sp", q, limit=10, fields=[], filters=few)
    assert [len(r) for r in out] == [3, 3, 3]  # fewer than k, not padded
    assert out == _raw_search(cluster, "sp", vecs[:3], limit=10,
                              filters=few)["documents"]
    none = {"operator": "AND", "conditions": [
        {"field": "price", "operator": "<", "value": -1}]}
    assert client.search("db", "sp", q, limit=10, fields=[],
                         filters=none) == [[], [], []]


def test_sdk_understands_routers_of_every_age(cluster, client, vecs,
                                               monkeypatch):
    """A router that does not know `columnar` answers `documents`; one
    from before the array form answers key lists beside flat scores."""
    q = [{"field": "emb", "feature": vecs[:4]}]
    want = client.search("db", "sp", q, limit=5, fields=[], cache=False)
    real = client._doc_call

    def documents_router(method, path, body):
        return real(method, path,
                    {k: v for k, v in body.items() if k != "columnar"})

    def key_lists_router(method, path, body):
        out = real(method, path, body)
        rows = hitarrays.to_rows(out)
        for name in hitarrays.ARRAYS:
            del out[name]
        out["keys"] = [[h["_id"] for h in r] for r in rows]
        out["scores"] = np.asarray(
            [h["_score"] for r in rows for h in r], np.float32)
        return out

    for fake in (documents_router, key_lists_router):
        monkeypatch.setattr(client, "_doc_call", fake)
        assert client.search("db", "sp", q, limit=5, fields=[],
                             cache=False) == want
        out = client.search("db", "sp", q, limit=5, fields=[], profile=True,
                            cache=False)
        assert out["documents"] == want and "profile" in out
        assert not set(out) & {"columnar", "keys", *hitarrays.ARRAYS}


def test_sorted_and_field_searches_keep_the_rows_form(cluster, client, vecs):
    q = vecs[:2]
    out = _raw_search(cluster, "sp", q, columnar=True,
                      sort=[{"price": "desc"}])
    assert "documents" in out and "_sort" in out["documents"][0][0]
    with_fields = client.search(
        "db", "sp", [{"field": "emb", "feature": q}], limit=3,
        fields=["price"])
    assert all("price" in h for r in with_fields for h in r)
    # a caller that asks `columnar` and nothing else gets the arrays
    out = _raw_search(cluster, "sp", q, columnar=True, limit=3)
    assert hitarrays.is_arrays(out) and out["counts"].tolist() == [3, 3]


def _form_counts(addr):
    out = {}
    for line in scrape(addr).splitlines():
        if line.startswith("vearch_search_replies_total{"):
            out[line.split('"')[1]] = float(line.rsplit(" ", 1)[1])
    return out


def test_reply_form_counter_reads_arrays_and_rows(cluster, client, vecs):
    addrs = [cluster.router_addr] + [ps.addr for ps in cluster.ps_nodes]
    q = [{"field": "emb", "feature": vecs[:2]}]
    before = {a: _form_counts(a) for a in addrs}
    assert all(set(c) == {"arrays", "rows"} for c in before.values())
    for _ in range(3):
        client.search("db", "sp", q, limit=3, fields=[], cache=False)
    client.search("db", "sp", q, limit=3, fields=["price"], cache=False)
    client.search("db", "sp", q, limit=3, cache=False)
    after = {a: _form_counts(a) for a in addrs}
    moved = {a: {f: after[a][f] - before[a][f] for f in ("arrays", "rows")}
             for a in addrs}
    assert moved[cluster.router_addr] == {"arrays": 3, "rows": 2}
    # two partitions, each on one of the two partition servers or both on
    # one: every partition answers every search once
    ps = [moved[a] for a in addrs[1:]]
    assert sum(m["arrays"] for m in ps) == 6
    assert sum(m["rows"] for m in ps) == 4
    # a caller that does not ask `columnar`: the partitions still answer
    # arrays, the router builds the rows
    _raw_search(cluster, "sp", vecs[:2], limit=3, cache=False)
    last = {a: _form_counts(a) for a in addrs}
    assert last[cluster.router_addr]["rows"] \
        == after[cluster.router_addr]["rows"] + 1
    assert sum(last[a]["arrays"] - after[a]["arrays"]
               for a in addrs[1:]) == 2


def test_caches_hand_out_the_form_each_request_asks(cluster, client, vecs):
    """The router's and the partition servers' result caches store
    replies: a hit serves the form its own request wants."""
    q = vecs[20:23]
    sdk_q = [{"field": "emb", "feature": q}]
    hits0 = rpc.call(cluster.router_addr, "GET",
                     "/router/stats")["result_cache"]["hit"]
    first = client.search("db", "sp", sdk_q, limit=4, fields=[])
    docs = _raw_search(cluster, "sp", q, limit=4)["documents"]
    again = client.search("db", "sp", sdk_q, limit=4, fields=[])
    docs_again = _raw_search(cluster, "sp", q, limit=4)
    assert first == docs == again == docs_again["documents"]
    assert "columnar" not in docs_again
    stats = rpc.call(cluster.router_addr, "GET", "/router/stats")
    assert stats["result_cache"]["hit"] == hits0 + 2


def test_rpc_encode_span_carries_form_and_bytes(cluster, client, vecs):
    out = client.search("db", "sp", [{"field": "emb", "feature": vecs[:2]}],
                        limit=3, fields=[], profile=True, cache=False)
    tid = out["trace_id"]
    deadline = time.monotonic() + 5.0
    while True:
        spans = [r for r in tracing.snapshot()
                 if r.trace_id == tid and r.name == "rpc.encode"]
        if len(spans) == 3:  # the router's, and one a partition
            break
        assert time.monotonic() < deadline, spans
        time.sleep(0.005)
    assert {s.service for s in spans} == {"router", "ps"}
    for s in spans:
        assert s.tags["form"] == "arrays" and s.tags["bytes"] > 0
    out = client.search("db", "sp", [{"field": "emb", "feature": vecs[:2]}],
                        limit=3, fields=["price"], profile=True, cache=False)
    tid = out["trace_id"]
    while True:
        spans = [r for r in tracing.snapshot()
                 if r.trace_id == tid and r.name == "rpc.encode"]
        if len(spans) == 3:
            break
        assert time.monotonic() < deadline + 5.0, spans
        time.sleep(0.005)
    assert {s.tags["form"] for s in spans} == {"rows"}
