"""SDK wire-fixture conformance (VERDICT r2 #8).

The Go/Java/Rust SDKs cannot be compiled here (no toolchains in the
image), so nothing used to catch a typo'd wire key in them. This module
closes that hole in two steps:

1. RECORD: drive the canonical operations through the Python SDK against
   a live cluster, capturing every request/response as a STRUCTURE
   (key tree with value types, not values — deterministic across runs)
   and compare against the committed fixture
   `sdk/fixtures/wire_shapes.json`. Server wire drift fails here first.
   Intentional changes: regenerate with VEARCH_UPDATE_FIXTURES=1.

2. ASSERT: for every wire key and route an SDK claims to speak, the
   exact quoted string must appear in that SDK's source. A typo'd
   struct tag (`json:"db_nam"`) or route fails the suite.

Reference intent: sdk/go, sdk/java, sdk/rust are CI-built upstream.
"""

import json
import os

import numpy as np
import pytest

from vearch_tpu.cluster import rpc as rpc_mod
from vearch_tpu.cluster.standalone import StandaloneCluster
from vearch_tpu.sdk.client import VearchClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "sdk", "fixtures", "wire_shapes.json")
D = 8


def shape_of(v):
    """Value -> deterministic structure: dicts keep keys, lists keep one
    element shape, scalars become type names."""
    if isinstance(v, np.ndarray):
        return "tensor"  # rides the binary codec, not JSON
    if isinstance(v, dict):
        return {k: shape_of(v[k]) for k in sorted(v)}
    if isinstance(v, (list, tuple)):
        return [shape_of(v[0])] if v else []
    if isinstance(v, bool):
        return "bool"
    if isinstance(v, int):
        return "int"
    if isinstance(v, float):
        return "float"
    if v is None:
        return "null"
    return "str"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Drive canonical ops; capture {op: {method, path, request,
    response}} wire structures."""
    import threading

    rec: dict[str, dict] = {}
    real_call = rpc_mod.call
    current_op: list[str] = [""]
    test_thread = threading.get_ident()

    def recording_call(addr, method, path, body=None, **kw):
        out = real_call(addr, method, path, body, **kw)
        # rpc.call is module-shared: the router's background watch poll
        # rides through here too — record only this thread's SDK calls
        if threading.get_ident() != test_thread:
            return out
        op = current_op[0]
        if op and op not in rec:
            rec[op] = {
                "method": method,
                # server-assigned path segments normalized
                "path": path,
                "request": shape_of(body) if body is not None else None,
                "response": shape_of(out),
            }
        return out

    import vearch_tpu.sdk.client as sdk_mod

    sdk_mod.rpc.call = recording_call
    try:
        with StandaloneCluster(
            data_dir=str(tmp_path_factory.mktemp("sdkfix")), n_ps=1
        ) as c:
            cl = VearchClient(c.router_addr)
            rng = np.random.default_rng(0)
            vecs = rng.standard_normal((20, D)).astype(np.float32)

            def op(name, fn):
                current_op[0] = name
                out = fn()
                current_op[0] = ""
                return out

            op("create_database", lambda: cl.create_database("db"))
            op("create_space", lambda: cl.create_space("db", {
                "name": "sp", "partition_num": 1, "replica_num": 1,
                "fields": [
                    {"name": "color", "data_type": "string"},
                    {"name": "price", "data_type": "float"},
                    {"name": "emb", "data_type": "vector", "dimension": D,
                     "index": {"index_type": "FLAT", "metric_type": "L2",
                               "params": {}}},
                ],
            }))
            op("get_space", lambda: cl.get_space("db", "sp"))
            op("upsert", lambda: cl.upsert("db", "sp", [
                {"_id": f"d{i}", "color": "red", "price": float(i),
                 "emb": vecs[i]} for i in range(20)
            ]))
            op("search", lambda: cl.search(
                "db", "sp", [{"field": "emb", "feature": vecs[1].tolist()}],
                limit=3,
                filters={"operator": "AND", "conditions": [
                    {"operator": "=", "field": "color", "value": "red"}]},
                fields=["color", "price"],
            ))
            # ids and scores only: the Python SDK asks the array form
            # of the reply (cluster/hitarrays.py); the Go/Java/Rust
            # SDKs do not, and keep the rows of "search"
            op("search_ids", lambda: cl.search(
                "db", "sp", [{"field": "emb", "feature": vecs[1].tolist()}],
                limit=3, fields=[]))
            op("query", lambda: cl.query("db", "sp",
                                         document_ids=["d1", "d2"]))
            op("delete", lambda: cl.delete("db", "sp",
                                           document_ids=["d1"]))
            op("flush", lambda: cl.flush("db", "sp"))
            op("add_field_index",
               lambda: cl.add_field_index("db", "sp", "color", "BITMAP",
                                          background=False))
            op("remove_field_index",
               lambda: cl.remove_field_index("db", "sp", "color"))
            op("list_databases", lambda: cl.list_databases())
    finally:
        sdk_mod.rpc.call = real_call
    return rec


def test_wire_shapes_match_committed_fixture(recorded):
    if os.environ.get("VEARCH_UPDATE_FIXTURES") == "1" \
            or not os.path.exists(FIXTURE):
        os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
        with open(FIXTURE, "w") as f:
            json.dump(recorded, f, indent=1, sort_keys=True)
    with open(FIXTURE) as f:
        committed = json.load(f)
    assert recorded == committed, (
        "wire structures drifted from sdk/fixtures/wire_shapes.json — "
        "if intentional, regenerate with VEARCH_UPDATE_FIXTURES=1 and "
        "update the non-Python SDKs to match"
    )


# which fixture ops each SDK implements, and the wire keys it must spell
# correctly for them (request keys it serializes + response keys it
# reads; projection fields like doc columns excluded)
_DOC_OPS = ("upsert", "search", "query", "delete", "flush")
_SDK_SURFACES = {
    "go/client.go": {
        "ops": _DOC_OPS + ("create_database", "create_space", "get_space"),
        "extra_keys": ["document_ids", "total", "documents", "_id",
                       "_score", "code", "msg", "data"],
    },
    # Java and Rust return the raw `data` payload (callers unwrap
    # result keys), so only the envelope is their response surface
    "java/src/main/java/io/vearchtpu/VearchTpuClient.java": {
        "ops": _DOC_OPS + ("create_database", "create_space"),
        "extra_keys": ["code", "msg", "data"],
        # createSpace(String spaceConfigJson): schema keys are caller
        # passthrough, not serialized by the SDK
        "passthrough_ops": {"create_space"},
    },
    "rust/src/lib.rs": {
        "ops": _DOC_OPS + ("create_database", "create_space"),
        "extra_keys": ["code", "msg", "data"],
    },
}

# request keys an SDK serializes for each op (top-level only; nested
# schema/filter keys are caller-provided passthrough in all three SDKs,
# except the universally-typed ones below)
_REQUEST_KEYS = {
    "upsert": ["db_name", "space_name", "documents"],
    "search": ["db_name", "space_name", "vectors", "limit", "filters",
               "fields", "field", "feature"],
    "query": ["db_name", "space_name", "document_ids", "limit"],
    "delete": ["db_name", "space_name", "document_ids"],
    "flush": ["db_name", "space_name"],
    "create_space": ["name", "fields", "partition_num", "replica_num",
                     "data_type", "dimension", "index"],
    "create_database": [],
    "get_space": [],
    "list_databases": [],
}

_ROUTES = {
    "upsert": "/document/upsert",
    "search": "/document/search",
    "query": "/document/query",
    "delete": "/document/delete",
    "flush": "/index/flush",
    "create_database": "/dbs",
    "create_space": "/spaces",
    "get_space": "/spaces",
}


def _tree_keys(node, out: set):
    if isinstance(node, dict):
        for k, v in node.items():
            out.add(k)
            _tree_keys(v, out)
    elif isinstance(node, list):
        for v in node:
            _tree_keys(v, out)


# wire keys of ops the fixture run does not exercise (partition rules,
# aliases, ranker, tracing, kill, backup) — kept curated so the reverse
# check below stays strict
_EXTRA_VALID = {
    "operator_type", "partition_name", "partition_rule", "type", "field",
    "ranges", "value", "min_score", "max_score", "boost", "ranker",
    "params", "weight",
    "load_balance", "request_id", "raft_consistent", "trace", "trace_id",
    "topn", "index_params", "anti_affinity", "enable_id_cache",
    "vector_value", "dbs", "spaces", "servers", "partitions", "alias",
    "code", "msg", "data",  # the response envelope itself
    "training_threshold", "refresh_interval_ms", "metric_type",
    "index_type", "store_type", "offset", "document_ids",
    # r5 full-surface additions (sort/pagination, membership, backup
    # jobs, RBAC, config, schedule ops) — all live server keys
    "sort", "order", "missing", "page_size", "page_num", "_sort",
    "node_id", "addr", "members", "leader",
    "command", "version", "versions", "async", "job_id", "store_root",
    "store", "status", "files_done", "files_total", "background",
    "partition_id", "method",
    "password", "role_name", "privileges", "name",
}


def _valid_wire_keys(recorded) -> set:
    valid: set = set(_EXTRA_VALID)
    for op in recorded.values():
        _tree_keys(op.get("request"), valid)
        _tree_keys(op.get("response"), valid)
    return valid


# per-SDK extraction of every wire key the source spells, for the
# reverse check: an SDK must not emit a key the server doesn't speak
_KEY_EXTRACTORS = {
    "go/client.go": [
        r'json:"([A-Za-z0-9_]+)',          # struct tags
        r'"([a-z_][a-z0-9_]*)":',          # inline map literals
    ],
    "java/src/main/java/io/vearchtpu/VearchTpuClient.java": [
        r'\\"([a-z_][a-z0-9_]*)\\":',      # string-built JSON keys
    ],
    "rust/src/lib.rs": [
        r'"([a-z_][a-z0-9_]*)"\s*:',       # json! macro keys
        r'insert\("([a-z_][a-z0-9_]*)"',   # map inserts
        r'pub ([a-z_][a-z0-9_]*):',        # serde-derived struct fields
    ],
}

# identifiers matched by the extractors that are not wire keys
_NON_WIRE = {"router_url", "auth", "agent"}  # rust Client struct fields


@pytest.mark.parametrize("sdk_file", sorted(_KEY_EXTRACTORS))
def test_sdk_emits_only_known_wire_keys(recorded, sdk_file):
    """Reverse conformance: every key the SDK spells must exist in the
    recorded wire structures (or the curated extra set). This is what
    catches a typo'd tag like json:"db_nam" — the forward check can be
    masked by a correct spelling elsewhere in the file."""
    import re

    with open(os.path.join(REPO, "sdk", sdk_file)) as f:
        src = f.read()
    valid = _valid_wire_keys(recorded) | _NON_WIRE
    emitted = set()
    for pat in _KEY_EXTRACTORS[sdk_file]:
        emitted.update(re.findall(pat, src))
    unknown = sorted(emitted - valid)
    assert not unknown, (
        f"{sdk_file} spells wire keys the server does not speak "
        f"(typo?): {unknown}"
    )


def _spells(src: str, key: str) -> bool:
    """Does the source serialize/read `key`? Accepts the exact quoted
    form ("key"), a Go/Java tag or option-suffixed form ("key,omitempty),
    and a serde-derived struct field (`pub key: T` / `key:` in json!)."""
    import re

    quoted = '"' + re.escape(key) + '["\',]'       # "key" / "key,omitempty
    field = r"\b" + re.escape(key) + r"\s*:"       # serde field / json! key
    escaped = f'\\"{key}\\"'                       # Java "...\"key\"..."
    return bool(
        re.search(quoted, src) or re.search(field, src) or escaped in src
    )


@pytest.mark.parametrize("sdk_file", sorted(_SDK_SURFACES))
def test_sdk_source_spells_wire_keys(recorded, sdk_file):
    path = os.path.join(REPO, "sdk", sdk_file)
    with open(path) as f:
        src = f.read()
    surface = _SDK_SURFACES[sdk_file]
    missing = []
    for op in surface["ops"]:
        assert op in recorded, f"fixture recorder lost op {op}"
        route = _ROUTES.get(op)
        if route and route not in src:
            missing.append(f"route {route} ({op})")
        if op in surface.get("passthrough_ops", set()):
            continue
        for key in _REQUEST_KEYS.get(op, []):
            if not _spells(src, key):
                missing.append(f'request key "{key}" ({op})')
    for key in surface["extra_keys"]:
        if not _spells(src, key):
            missing.append(f'response key "{key}"')
    assert not missing, (
        f"{sdk_file} does not spell these wire strings (typo or missing "
        f"op): {missing}"
    )


# -- full-route coverage vs OpenAPI (r4 review next-8) -----------------------
#
# Every route the OpenAPI document advertises must appear (as its static
# prefix) in all three non-Python SDK sources. Routes with no SDK
# surface anywhere (debug/metrics/PS-port internals) are excluded with
# reasons.

_ROUTE_EXCLUDES = {
    "/metrics",        # Prometheus scrapers, not SDK clients
    "/debug/stacks",   # operator debugging surface
    "/ps/kill",        # PS-port internal (reference SDKs lack it too)
    "/ps/requests",    # PS-port internal
    "/cache/dbs",      # router cache introspection, internal
    "/clean_lock",     # Go covers it; a JSON-string client adds no value
    "/schedule/fail_server",  # DELETE variant covered via the list route
}


def _openapi_route_prefixes() -> list[str]:
    """Static prefixes of every documented path ('/dbs/{db}/spaces' ->
    '/dbs/', plus distinctive literal segments like '/spaces')."""
    import re

    with open(os.path.join(REPO, "api", "openapi.yaml")) as f:
        paths = re.findall(r"^  (/[^\s:]+):", f.read(), re.M)
    out = []
    for p in paths:
        static = p.split("{")[0].rstrip("/")
        if not static:
            continue
        if any(static == e or static.startswith(e + "/")
               for e in _ROUTE_EXCLUDES):
            continue
        out.append(static)
    return sorted(set(out))


@pytest.mark.parametrize("sdk_file", sorted(_KEY_EXTRACTORS))
def test_sdk_covers_every_openapi_route(sdk_file):
    with open(os.path.join(REPO, "sdk", sdk_file)) as f:
        src = f.read()
    missing = [r for r in _openapi_route_prefixes() if r not in src]
    assert not missing, (
        f"{sdk_file} lacks OpenAPI routes: {missing} — every documented "
        "route must appear in all three SDKs (r4 review next-8)"
    )


def test_error_envelope_and_auth_header_shapes(tmp_path):
    """The error envelope ({code, msg}) and BasicAuth header the three
    SDKs implement, pinned against the live server."""
    import base64
    import urllib.request

    from vearch_tpu.cluster.master import MasterServer

    m = MasterServer(auth=True, root_password="pw")
    m.start()
    try:
        # error envelope: wrong credentials -> 401 code + msg keys
        req = urllib.request.Request(
            f"http://{m.addr}/dbs", method="GET",
            headers={"Authorization": "Basic " + base64.b64encode(
                b"root:wrong").decode()})
        body = json.loads(urllib.request.urlopen(req).read())
        assert shape_of(body) == {"code": "int", "msg": "str"}
        assert body["code"] == 401
        # the exact header scheme all three SDKs build
        req = urllib.request.Request(
            f"http://{m.addr}/dbs", method="GET",
            headers={"Authorization": "Basic " + base64.b64encode(
                b"root:pw").decode()})
        ok = json.loads(urllib.request.urlopen(req).read())
        assert ok["code"] == 0 and "data" in ok
        # 404 error envelope has the same shape
        req = urllib.request.Request(
            f"http://{m.addr}/dbs/nope", method="GET",
            headers={"Authorization": "Basic " + base64.b64encode(
                b"root:pw").decode()})
        nf = json.loads(urllib.request.urlopen(req).read())
        assert shape_of(nf) == {"code": "int", "msg": "str"}
        assert nf["code"] == 404
    finally:
        m.stop()


def test_all_sdks_spell_auth_and_envelope():
    """Each SDK must build 'Authorization: Basic <b64(user:password)>'
    and read the {code, msg, data} envelope."""
    for sdk_file in _KEY_EXTRACTORS:
        with open(os.path.join(REPO, "sdk", sdk_file)) as f:
            src = f.read()
        assert "Authorization" in src and "Basic " in src, sdk_file
        for key in ("code", "msg", "data"):
            assert _spells(src, key), (sdk_file, key)


# -- framework integrations (reference: sdk/integrations/*) ------------------

def test_integration_adapters_use_real_sdk_methods():
    """langchaingo / LangChain4j adapters are source-only (no
    toolchains); pin them to the SDK surface they call so an SDK rename
    breaks HERE, not in a consumer's build."""
    import re

    go_sdk = open(os.path.join(REPO, "sdk", "go", "client.go")).read()
    go_methods = set(re.findall(r"func \(c \*Client\) (\w+)\(", go_sdk))
    adapter = open(os.path.join(
        REPO, "sdk", "integrations", "langchaingo", "vearchtpu.go")).read()
    called = set(re.findall(r"\.client\.(\w+)\(", adapter))
    assert called and called <= go_methods, (
        f"langchaingo adapter calls unknown Go SDK methods: "
        f"{sorted(called - go_methods)}"
    )
    # struct fields the adapter reads from SDK types must exist
    for name in ("SpaceConfig", "SearchVector", "SearchRequest",
                 "Document", "APIError"):
        assert f"vearch.{name}" in adapter or name in go_methods, name

    java_sdk = open(os.path.join(
        REPO, "sdk", "java", "src", "main", "java", "io", "vearchtpu",
        "VearchTpuClient.java")).read()
    java_methods = set(re.findall(
        r"public \w+(?:<[^>]+>)? (\w+)\(", java_sdk))
    j_adapter = open(os.path.join(
        REPO, "sdk", "integrations", "langchain4j", "src", "main",
        "java", "io", "vearchtpu", "langchain4j",
        "VearchTpuEmbeddingStore.java")).read()
    j_called = set(re.findall(r"client\.(\w+)\(", j_adapter))
    assert j_called and j_called <= java_methods, (
        f"langchain4j adapter calls unknown Java SDK methods: "
        f"{sorted(j_called - java_methods)}"
    )
