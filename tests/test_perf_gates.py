"""Hardware-independent count gates (r6 tentpole).

Counts that repeat exactly on any backend are asserted HERE, on the CPU
backend, the way recall is gated (they are counts, not speeds — a speed
comes only from a chip run, see PERF.md):

- dispatch counts: each search path launches exactly its documented
  number of device programs (ops/perf_model.py DOCUMENTED_DISPATCHES);
- compiled-program stability: warmup pre-traces the configured batch
  buckets, after which repeated same-shape searches add ZERO new
  compiled programs (no silent retrace on the hot path);
- bytes materialized: the full scan's peak intermediate HBM is the
  [B, N] f32 score matrix, and the mirror streams exactly once;
- HBM footprint: the per-index capacity model tracks the real device
  state the index publishes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vearch_tpu.engine.engine import Engine, SearchRequest
from vearch_tpu.engine.types import (
    DataType,
    FieldSchema,
    IndexParams,
    MetricType,
    TableSchema,
)
from vearch_tpu.ops import ivf as ivf_ops
from vearch_tpu.ops import perf_model

D = 32
N = 3000


def _build(index_type, params, n=N, warmup=None, data_dir=None):
    params = dict(params)
    if warmup:
        params["warmup_batches"] = warmup
    schema = TableSchema("t", [
        FieldSchema("group", DataType.INT),
        FieldSchema("emb", DataType.VECTOR, dimension=D,
                    index=IndexParams(index_type, MetricType.L2, params)),
    ])
    eng = Engine(schema, data_dir=data_dir)
    rng = np.random.default_rng(33)
    vecs = rng.standard_normal((n, D), dtype=np.float32)
    eng.upsert([
        {"_id": f"d{i:05d}", "group": i % 4, "emb": vecs[i]}
        for i in range(n)
    ])
    eng.build_index()
    eng.wait_for_index()
    return eng, vecs


IVFPQ_PARAMS = {
    "ncentroids": 16, "nsubvector": 8, "train_iters": 4,
    "training_threshold": 256,
    # single-device ledger gates; the mesh path has its own gates in
    # test_mesh_serving.py (conftest forces 8 devices → auto would mesh)
    "mesh_serving": "off",
}


@pytest.fixture(scope="module")
def ivfpq_engine():
    return _build("IVFPQ", IVFPQ_PARAMS, warmup=[8])


def _search(eng, vecs, b=8, index_params=None):
    """One engine search under a fresh PerfLedger; returns the ledger."""
    ledger = perf_model.PerfLedger()
    ivf_ops.set_dispatch_ledger(ledger)
    try:
        eng.search(SearchRequest(
            vectors={"emb": vecs[:b]}, k=10, include_fields=[],
            index_params=index_params or {},
        ))
    finally:
        ivf_ops.set_dispatch_ledger(None)
    return ledger


# -- gate 1: dispatch count per search path ----------------------------------


def test_ivfpq_paths_launch_documented_dispatches(ivfpq_engine, tmp_path):
    eng, vecs = ivfpq_engine
    doc = perf_model.DOCUMENTED_DISPATCHES
    # the two-step full scan is what a disk store takes: the rerank
    # gathers its rows on the host
    disk_eng, _ = _build(
        "IVFPQ", {**IVFPQ_PARAMS, "store_type": "RocksDB"}, n=1000,
        data_dir=str(tmp_path))
    cases = {
        "ivfpq_full_fused": (eng, {"scan_mode": "full"}),
        "ivfpq_full_unfused": (disk_eng, {"scan_mode": "full"}),
        "ivfpq_probe": (eng, {"scan_mode": "probe"}),
    }
    for path, (engine, params) in cases.items():
        ledger = _search(engine, vecs, index_params=params)
        assert ledger.tags == doc[path], (
            f"{path}: launched {ledger.tags}, documented {doc[path]} — "
            "a new dispatch on a serving path must bump "
            "DOCUMENTED_DISPATCHES in the same PR"
        )
    disk_eng.close()


@pytest.fixture(scope="module")
def path_indexes(tmp_path_factory):
    """One corpus under the three things `_serving_path` can observe of
    an index: a RAM store, a disk store, and SCANN with
    `reordering: false` (no exact rerank wanted)."""
    from vearch_tpu.engine.disk_vector import DiskRawVectorStore
    from vearch_tpu.engine.raw_vector import RawVectorStore
    from vearch_tpu.index.registry import create_index

    vecs = np.random.default_rng(5).standard_normal(
        (N, D)).astype(np.float32)
    shape = {"ncentroids": 16, "nsubvector": 8, "train_iters": 4}
    stores = {
        "ram": ("IVFPQ", shape, RawVectorStore(D)),
        "disk": ("IVFPQ", shape, DiskRawVectorStore(
            D, str(tmp_path_factory.mktemp("paths") / "store"))),
        "scann": ("SCANN", {**shape, "reordering": False},
                  RawVectorStore(D)),
    }
    built = {}
    for name, (kind, params, store) in stores.items():
        store.add(vecs)
        idx = create_index(IndexParams(kind, MetricType.L2, params), store)
        idx.train(vecs)
        idx.absorb(N)
        built[name] = idx
    return built, vecs


# conftest.py shows the process 8 devices: "auto" meshes, and the
# full-scan limit of a mesh counts 8 times (its data axis)
@pytest.mark.parametrize("index,params,limit,devices,path", [
    ("ram", {"mesh_serving": "off"}, 16_000_000, 8, "ivfpq_full_fused"),
    ("ram", {"mesh_serving": "off"}, N - 1, 8, "ivfpq_probe"),
    ("ram", {"mesh_serving": "off", "scan_mode": "full"}, N - 1, 8,
     "ivfpq_full_fused"),
    ("ram", {"mesh_serving": "off", "scan_mode": "probe"}, 16_000_000, 8,
     "ivfpq_probe"),
    ("ram", {}, 16_000_000, 1, "ivfpq_full_fused"),
    ("ram", {}, 16_000_000, 8, "ivfpq_mesh_fused"),
    ("ram", {"mesh_serving": "on"}, N // 8, 8, "ivfpq_mesh_fused"),
    ("ram", {"mesh_serving": "on"}, N // 8 - 1, 8, "ivfpq_mesh_probe"),
    ("ram", {"mesh_serving": "on", "scan_mode": "probe"}, 16_000_000, 8,
     "ivfpq_mesh_probe"),
    ("disk", {"mesh_serving": "off"}, 16_000_000, 8, "ivfpq_full_unfused"),
    ("disk", {}, 16_000_000, 8, "ivfpq_full_unfused"),
    ("disk", {"mesh_serving": "on"}, N - 1, 8, "ivfpq_probe"),
    ("scann", {"mesh_serving": "off"}, 16_000_000, 8, "ivfpq_full_unfused"),
    ("scann", {}, 16_000_000, 8, "ivfpq_mesh_scan"),
    ("scann", {"scan_mode": "probe"}, 16_000_000, 8, "ivfpq_probe"),
    ("scann", {"mesh_serving": "off", "rerank": 64}, 16_000_000, 8,
     "ivfpq_full_fused"),
])
def test_serving_path_is_chosen_from_what_the_index_observes(
        path_indexes, monkeypatch, index, params, limit, devices, path):
    """`IVFPQIndex._serving_path` names the documented path from the
    store, the devices visible against `mesh_serving`, the rows against
    the per-chip limit (or `scan_mode`) and whether a rerank is wanted,
    and the search launches exactly that path's programs. Where no
    rerank is wanted the two-step paths stop before theirs."""
    import jax

    built, vecs = path_indexes
    idx = built[index]
    monkeypatch.setattr(idx, "full_scan_limit", limit)
    visible = jax.devices()[:devices]
    monkeypatch.setattr(jax, "devices", lambda *a: visible)
    assert idx._serving_path(params) == path
    ledger: list = []
    ivf_ops.set_dispatch_ledger(ledger)
    try:
        _, ids = idx.search(vecs[:8], 10, None, params)
    finally:
        ivf_ops.set_dispatch_ledger(None)
    want = perf_model.DOCUMENTED_DISPATCHES[path]
    if not idx._exact_rerank_enabled(params):
        want = [tag for tag in want if tag != "rerank"]
    assert ledger == want
    assert list(ids[:, 0]) == list(range(8))


def test_graft_entry_returns_the_served_program():
    """The driver's compile check (`__graft_entry__.entry()`) lowers
    what the one-chip cells serve, under the module name their device
    trace shows, with the selection's two levels in it: four `top_k`s
    (block maxima, group maxima, the chosen groups' scores, rerank)."""
    import jax

    import __graft_entry__

    fn, args = __graft_entry__.entry()
    text = jax.jit(fn).lower(*args).as_text()
    assert text.startswith("module @jit_int8_scan_rerank ")
    assert "@int8_scan_candidates" in text and "@exact_rerank" in text
    assert text.count("chlo.top_k") == 4
    assert len(args) == 7 and args[5].shape == (args[1].shape[0], 128)


def test_ivfflat_and_flat_dispatch_counts():
    doc = perf_model.DOCUMENTED_DISPATCHES
    eng, vecs = _build("IVFFLAT", {
        "ncentroids": 16, "train_iters": 4, "training_threshold": 256,
    })
    assert _search(eng, vecs).tags == doc["ivfflat"]
    feng, fvecs = _build("FLAT", {}, n=500)
    assert _search(feng, fvecs).tags == doc["flat"]


def test_ivfflat_served_path_documented_dispatch_and_zero_new_programs(
        tmp_path):
    """The `ivfflat` row through the served path (client -> router -> PS
    -> engine -> `IVFFlatIndex.search`): each search launches exactly
    `ivfflat_scan`, and once the row counts a mix can put into one
    dispatch (benchmark/traffic/b64x4-closed.json `warm_rows`: 64, 128,
    256) have been served, repeating them adds ZERO compiled programs
    (`window_compiles` 0 in the benchmark's cell)."""
    from vearch_tpu.cluster import rpc
    from vearch_tpu.cluster.standalone import StandaloneCluster
    from vearch_tpu.sdk.client import VearchClient

    n, doc = 2000, perf_model.DOCUMENTED_DISPATCHES
    c = StandaloneCluster(data_dir=str(tmp_path / "c"), n_ps=1)
    c.start()
    try:
        cl = VearchClient(c.router_addr)
        cl.create_database("db")
        cl.create_space("db", {
            "name": "s", "partition_num": 1, "fields": [
                {"name": "v", "data_type": "vector", "dimension": D,
                 "index": {"index_type": "IVFFLAT", "metric_type": "L2",
                           "params": {"ncentroids": 16, "train_iters": 4,
                                      "training_threshold": n}}}]})
        vecs = np.random.default_rng(17).standard_normal(
            (n, D)).astype(np.float32)
        cl.upsert("db", "s", [{"_id": f"d{i}", "v": vecs[i]}
                              for i in range(n)])
        ps = c.ps_nodes[0]
        (pid, engine), = ps.engines.items()
        engine.wait_for_index(timeout=300)
        # the shadow-recall sampler's exact scans (`flat_scan`, in a
        # background thread) are off, as in the benchmark's cells
        rpc.call(ps.addr, "POST", "/ps/engine/config", {
            "partition_id": int(pid),
            "config": {"quality": {"sample_rate": 0.0}}})

        def search(rows):
            out = cl.search(
                "db", "s", vectors=[{"field": "v", "feature": vecs[:rows]}],
                limit=10, fields=[], profile=True, cache=False,
                index_params={"nprobe": 4, "rerank": 256})
            (part,) = out["profile"]["partitions"].values()
            assert part["dispatches"]["tags"] == doc["ivfflat"], part
            assert part["dispatches"]["path"] == "ivfflat"

        for rows in (64, 128, 256):
            search(rows)
        before = perf_model.compiled_program_counts()
        ledger = perf_model.PerfLedger()
        ivf_ops.set_dispatch_ledger(ledger)
        try:
            for rows in (64, 128, 256, 256, 128, 64):
                search(rows)
        finally:
            ivf_ops.set_dispatch_ledger(None)
        assert ledger.tags == doc["ivfflat"] * 6
        assert perf_model.compiled_program_counts() == before
    finally:
        c.stop()


def test_ledger_per_search_aggregation(ivfpq_engine):
    eng, vecs = ivfpq_engine
    ledger = perf_model.PerfLedger()
    ivf_ops.set_dispatch_ledger(ledger)
    try:
        for _ in range(3):
            eng.search(SearchRequest(
                vectors={"emb": vecs[:8]}, k=10, include_fields=[],
                index_params={"scan_mode": "full"}))
            ledger.mark_search()
    finally:
        ivf_ops.set_dispatch_ledger(None)
    assert ledger.per_search() == [["fused_scan_rerank"]] * 3
    assert ledger.dispatch_count() == 3
    assert ledger.counts() == {"fused_scan_rerank": 3}


# -- gate 2: compiled-program stability --------------------------------------


def test_warmup_then_zero_new_programs(ivfpq_engine):
    """build_index warmed b=8 (warmup_batches): the serving shapes are
    already traced+compiled, so repeated b=8 searches add ZERO compiled
    programs — the first real query never pays a compile stall."""
    eng, vecs = ivfpq_engine
    _search(eng, vecs, b=8)  # settle any first-use side programs
    before = perf_model.total_compiled_programs()
    for _ in range(3):
        _search(eng, vecs, b=8)
    after = perf_model.total_compiled_programs()
    assert after == before, (
        f"repeated same-shape searches grew the jit cache "
        f"{before} -> {after}: something retraces per request"
    )


def test_compiled_program_counts_cover_registry():
    counts = perf_model.compiled_program_counts()
    # the serving entry points are registered and introspectable
    # (-1 would mean jit internals moved under us)
    for name in ("ivf.int8_scan_rerank", "ivf.ivfpq_candidates",
                 "distance.brute_force_search"):
        assert name in counts
        assert counts[name] >= 0


def test_explicit_warmup_pretraces_new_batch_size(ivfpq_engine):
    eng, vecs = ivfpq_engine
    # warmup quantizes the requested batch to its row bucket (5 -> 8):
    # serving pads the same way, so the traced shape is the served shape
    done = eng.warmup(batches=[5])
    assert done == {"emb": [perf_model.bucket_rows(5)]}
    before = perf_model.total_compiled_programs()
    _search(eng, vecs, b=5)
    assert perf_model.total_compiled_programs() == before


def test_warmed_mixed_k_nprobe_workload_zero_new_programs(ivfpq_engine):
    """The continuous-batching gate: once the declared shape buckets a
    workload can touch are warm, a concurrent mixed-(k, nprobe) request
    stream adds ZERO compiled programs — traffic entropy lands on the
    quantized grid, never on fresh XLA specializations."""
    import threading

    eng, vecs = ivfpq_engine
    # warm the bucket grid the workload can reach: row buckets 8 and 64
    # (32 workers x <=2 rows never exceeds 64), fetch-k tier 16
    # (k in {4, 7, 10}), both nprobe variants
    for b in (8, 64):
        for params in ({}, {"nprobe": 4}):
            _search(eng, vecs, b=b, index_params=params)
    before = perf_model.total_compiled_programs()

    errs = []

    def worker(i):
        rows = 1 + i % 2
        try:
            eng.search(SearchRequest(
                vectors={"emb": vecs[i : i + rows]},
                k=(4, 7, 10)[i % 3], include_fields=[],
                index_params={} if i % 2 else {"nprobe": 4},
            ))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    after = perf_model.total_compiled_programs()
    assert after == before, (
        f"warmed mixed-(k, nprobe) traffic grew the jit cache "
        f"{before} -> {after}: a request shape escaped the bucket grid"
    )


def test_dispatches_bounded_by_bucket_capacity(ivfpq_engine):
    """Same-bucket traffic needs at most ceil(requests / capacity)
    dispatches (perf_model.bucket_dispatch_bound): buckets seal exactly
    at capacity, so 16 single-row requests through an 8-row bucket are
    two full dispatches, never sixteen solos."""
    import threading

    from vearch_tpu.engine.batching import BatchScheduler

    eng, vecs = ivfpq_engine
    # age bound far beyond the test: only FULL buckets may dispatch,
    # making the dispatch count deterministic
    mb = BatchScheduler(eng, max_rows=8, max_delay_ms=3_600_000.0)
    try:
        n = 16
        errs = []

        def worker(i):
            try:
                mb.submit(SearchRequest(
                    vectors={"emb": vecs[i]}, k=10, include_fields=[]))
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i,),
                                    daemon=True) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errs
        want = perf_model.bucket_dispatch_bound(n, 8)
        assert want == 2
        assert mb.dispatches == want, (
            f"{n} same-bucket requests took {mb.dispatches} dispatches; "
            f"the perf model allows {want}"
        )
        assert mb.full_dispatches == want
    finally:
        mb.stop()


def test_bucket_model_helpers():
    """The quantization model the scheduler and warmup share."""
    assert [perf_model.bucket_rows(b) for b in (1, 8, 9, 64, 65, 1024)] \
        == [8, 8, 64, 64, 256, 1024]
    assert [perf_model.bucket_fetch_k(k) for k in (3, 16, 17, 1024)] \
        == [16, 16, 64, 1024]
    # out-of-grid sizes pass through unchanged (caller-bounded)
    assert perf_model.bucket_rows(5000) == 5000
    assert perf_model.bucket_fetch_k(5000) == 5000
    assert perf_model.bucket_program_bound() == len(
        perf_model.ROW_BUCKETS) * len(perf_model.FETCH_K_TIERS)
    assert perf_model.bucket_dispatch_bound(17, 8) == 3
    assert perf_model.padding_waste_bytes(3, 8, 32) == 5 * 32 * 4


def test_deadline_and_slowlog_capture_add_zero_device_work(ivfpq_engine):
    """Arming a per-request deadline and the slowlog's forced phase
    capture (trace dict) is pure host-side bookkeeping: the warmed
    serving path must launch the identical dispatch sequence and add
    ZERO compiled programs versus a plain search."""
    import time as _time

    from vearch_tpu.engine.engine import RequestContext

    eng, vecs = ivfpq_engine
    params = {"scan_mode": "full"}
    _search(eng, vecs, b=8, index_params=params)  # settle first-use
    plain = _search(eng, vecs, b=8, index_params=params)
    before = perf_model.total_compiled_programs()

    ledger = perf_model.PerfLedger()
    ivf_ops.set_dispatch_ledger(ledger)
    try:
        eng.search(SearchRequest(
            vectors={"emb": vecs[:8]}, k=10, include_fields=[],
            index_params=params,
            # exactly what the PS arms when a deadline or a slowlog
            # threshold is set: a deadline-bearing context checked
            # between dispatches + a forced trace dict
            trace={},
            ctx=RequestContext("perf-gate",
                               deadline=_time.time() + 60.0),
        ))
    finally:
        ivf_ops.set_dispatch_ledger(None)
    assert ledger.tags == plain.tags, (
        f"armed search launched {ledger.tags} vs plain {plain.tags}: "
        "deadline/slowlog instrumentation reached the device"
    )
    assert perf_model.total_compiled_programs() == before, (
        "deadline/slowlog instrumentation compiled new programs on the "
        "warmed serving path"
    )


# -- gate 2b: cache-hit dispatch gates (docs/PERF.md "Tier 4") ---------------


def test_cached_search_adds_zero_dispatches_and_zero_programs(tmp_path):
    """The serving-cache contract, stated on the device ledger: once a
    query is cached, REPEATING it performs zero engine dispatches and
    compiles zero new programs — and the engine's filter-bitmap cache
    stops re-evaluating an identical filter even on cache-bypassing
    requests."""
    from vearch_tpu.cluster import rpc
    from vearch_tpu.cluster.standalone import StandaloneCluster
    from vearch_tpu.sdk.client import VearchClient

    d = 16
    c = StandaloneCluster(data_dir=str(tmp_path / "c"), n_ps=1)
    c.start()
    try:
        cl = VearchClient(c.router_addr)
        cl.create_database("db")
        cl.create_space("db", {
            "name": "s", "partition_num": 1,
            "fields": [
                {"name": "group", "data_type": "integer"},
                {"name": "v", "data_type": "vector", "dimension": d,
                 "index": {"index_type": "FLAT", "metric_type": "L2",
                           "params": {}}},
            ],
        })
        rng = np.random.default_rng(9)
        vecs = rng.standard_normal((200, d)).astype(np.float32)
        cl.upsert("db", "s", [
            {"_id": f"d{i}", "group": i % 4, "v": vecs[i]}
            for i in range(200)
        ])

        def search(**extra):
            return rpc.call(c.router_addr, "POST", "/document/search", {
                "db_name": "db", "space_name": "s",
                "vectors": [{"field": "v", "feature": q.tolist()}
                            for q in vecs[:2]],
                "limit": 5, **extra,
            })

        search()  # cold: compiles, dispatches, populates every tier
        before = perf_model.total_compiled_programs()
        ledger = perf_model.PerfLedger()
        ivf_ops.set_dispatch_ledger(ledger)
        try:
            for _ in range(5):
                search()
        finally:
            ivf_ops.set_dispatch_ledger(None)
        assert ledger.tags == [], (
            f"repeated identical searches reached the device: "
            f"{ledger.tags}"
        )
        assert perf_model.total_compiled_programs() == before, (
            "a cache hit compiled new programs"
        )

        # filter-bitmap tier: identical filters on cache-bypassing
        # requests still dispatch the scan but never re-evaluate the
        # filter against the current data version
        eng = c.ps_nodes[0].engines[next(iter(c.ps_nodes[0].engines))]
        filt = {"operator": "AND", "conditions": [
            {"field": "group", "operator": ">=", "value": 2}]}
        search(filters=filt, cache=False)  # miss: evaluates + caches
        hits0 = eng.filter_cache_hits
        ledger = perf_model.PerfLedger()
        ivf_ops.set_dispatch_ledger(ledger)
        try:
            search(filters=filt, cache=False)
        finally:
            ivf_ops.set_dispatch_ledger(None)
        assert eng.filter_cache_hits == hits0 + 1
        assert ledger.counts() == {"flat_scan": 1}  # bypass DID dispatch
    finally:
        c.stop()


def test_partition_split_adds_zero_dispatches_to_serving(tmp_path):
    """The elasticity contract on the device ledger: an entire online
    partition split is host-side work (engine key scans, doc re-reads,
    child-forward RPCs) — it launches ZERO device dispatches of its
    own, and once the post-split shapes have settled, repeated
    identical searches against the children again dispatch nothing and
    compile nothing (the router cache serves them)."""
    from vearch_tpu.cluster.standalone import StandaloneCluster
    from vearch_tpu.sdk.client import VearchClient

    d = 16
    c = StandaloneCluster(data_dir=str(tmp_path / "c"), n_ps=1)
    c.start()
    try:
        cl = VearchClient(c.router_addr, master_addr=c.master_addr)
        cl.create_database("db")
        cl.create_space("db", {
            "name": "s", "partition_num": 1,
            "fields": [
                {"name": "v", "data_type": "vector", "dimension": d,
                 "index": {"index_type": "FLAT", "metric_type": "L2",
                           "params": {}}},
            ],
        })
        rng = np.random.default_rng(11)
        vecs = rng.standard_normal((300, d)).astype(np.float32)
        cl.upsert("db", "s", [{"_id": f"d{i}", "v": vecs[i]}
                              for i in range(300)])
        parent = cl.get_space("db", "s")["partitions"][0]["id"]
        cl.search("db", "s", [{"field": "v", "feature": vecs[0]}],
                  limit=5)  # warm the pre-split serving path

        # the split itself: zero device work
        ledger = perf_model.PerfLedger()
        ivf_ops.set_dispatch_ledger(ledger)
        try:
            job = cl.split_partition("db", "s", parent, timeout_s=120.0)
            cl.wait_elastic_job(job["job_id"], timeout_s=120.0)
        finally:
            ivf_ops.set_dispatch_ledger(None)
        assert ledger.tags == [], (
            f"partition split reached the device: {ledger.tags}"
        )

        # settle the post-split shapes (children are new engines; the
        # first search may trace), then gate steady state
        cl.search("db", "s", [{"field": "v", "feature": vecs[0]}],
                  limit=5)
        before = perf_model.total_compiled_programs()
        ledger = perf_model.PerfLedger()
        ivf_ops.set_dispatch_ledger(ledger)
        try:
            for _ in range(5):
                cl.search("db", "s",
                          [{"field": "v", "feature": vecs[0]}], limit=5)
        finally:
            ivf_ops.set_dispatch_ledger(None)
        assert ledger.tags == [], (
            f"post-split repeated searches reached the device: "
            f"{ledger.tags}"
        )
        assert perf_model.total_compiled_programs() == before, (
            "post-split warmed searches compiled new programs"
        )
    finally:
        c.stop()


# -- gate 2c: tail-latency paths on the device ledger ------------------------


def test_hedged_and_replica_routed_search_add_zero_device_work(tmp_path):
    """The tail-latency contract on the device ledger: a hedged search
    dispatches exactly the documented count ONCE (the winner's) — the
    cancelled loser dies in its host-side wait and never reaches the
    device — and a replica-routed (least_loaded) search is the same
    documented dispatch sequence as a leader read. Neither compiles a
    new program on the warmed path."""
    import time as _time

    from vearch_tpu.cluster import rpc
    from vearch_tpu.cluster.standalone import StandaloneCluster
    from vearch_tpu.sdk.client import VearchClient

    d = 16
    c = StandaloneCluster(data_dir=str(tmp_path / "c"), n_ps=2,
                          router_kwargs={"hedge_quantile": 0.5,
                                         "hedge_budget_pct": 100.0,
                                         "hedge_min_delay_ms": 2.0})
    c.start()
    try:
        cl = VearchClient(c.router_addr)
        cl.create_database("db")
        cl.create_space("db", {
            "name": "s", "partition_num": 1, "replica_num": 2,
            "fields": [
                {"name": "v", "data_type": "vector", "dimension": d,
                 "index": {"index_type": "FLAT", "metric_type": "L2",
                           "params": {}}},
            ],
        })
        rng = np.random.default_rng(21)
        vecs = rng.standard_normal((100, d)).astype(np.float32)
        cl.upsert("db", "s", [{"_id": f"d{i}", "v": vecs[i]}
                              for i in range(100)])

        def search(lb=None):
            q = rng.standard_normal(d).astype(np.float32)
            body = {
                "db_name": "db", "space_name": "s",
                "vectors": [{"field": "v", "feature": q.tolist()}],
                "limit": 5,
            }
            if lb:
                body["load_balance"] = lb
            return rpc.call(c.router_addr, "POST", "/document/search",
                            body)

        # warm the hedge sketch past min-samples AND settle first-use
        # programs on BOTH replicas' engines (leader + not_leader)
        for _ in range(25):
            search()
        for _ in range(3):
            search(lb="not_leader")

        part = cl.get_space("db", "s")["partitions"][0]
        ps = next(p for p in c.ps_nodes if p.node_id == part["leader"])
        # the injected delay is the kill's headroom: the loser must be
        # cancelled before it wakes, and under CPU load the winner's
        # round trip (which triggers the kill) can take hundreds of ms
        # — a tight 500ms window made this a timing flake, not a gate
        rpc.call(ps.addr, "POST", "/ps/engine/config", {
            "partition_id": part["id"],
            "config": {"debug_search_delay_ms": 2500},
        })
        doc = perf_model.DOCUMENTED_DISPATCHES["flat"]
        n = 5
        before = perf_model.total_compiled_programs()
        ledger = perf_model.PerfLedger()
        ivf_ops.set_dispatch_ledger(ledger)
        try:
            for _ in range(n):
                out = search()
                assert out["documents"]
            # an un-cancelled loser would wake from its 2.5s injected
            # wait and dispatch inside this drain window — keep the
            # ledger armed so that bug cannot hide in a detach race
            _time.sleep(3.0)
        finally:
            ivf_ops.set_dispatch_ledger(None)
            rpc.call(ps.addr, "POST", "/ps/engine/config", {
                "partition_id": part["id"],
                "config": {"debug_search_delay_ms": 0},
            })
        stats = rpc.call(c.router_addr, "GET", "/router/stats")
        assert stats["hedges"]["fired"] >= n, stats["hedges"]
        assert ledger.counts() == {t: n * doc.count(t) for t in doc}, (
            f"hedged searches launched {ledger.counts()}, documented "
            f"{doc} x{n} — the cancelled attempt reached the device"
        )
        assert perf_model.total_compiled_programs() == before, (
            "a hedged search compiled new programs on the warmed path"
        )

        # replica-routed read: identical documented dispatch sequence.
        # The aggressive hedge knobs above stay live, so under CPU load
        # a phase-2 search can legitimately hedge (no injected delay —
        # the loser may reach the device before the kill lands): bound
        # the ledger by the hedges that actually fired instead of
        # assuming none do.
        fired0 = stats["hedges"]["fired"]
        ledger = perf_model.PerfLedger()
        ivf_ops.set_dispatch_ledger(ledger)
        try:
            for _ in range(n):
                search(lb="least_loaded")
        finally:
            ivf_ops.set_dispatch_ledger(None)
        fired = rpc.call(c.router_addr, "GET",
                         "/router/stats")["hedges"]["fired"] - fired0
        got = ledger.counts()
        want = {t: n * doc.count(t) for t in doc}
        cap = {t: (n + fired) * doc.count(t) for t in doc}
        assert set(got) == set(want) and all(
            want[t] <= got[t] <= cap[t] for t in want
        ), f"least_loaded searches launched {got}, documented {want} " \
           f"with {fired} hedges fired"
        assert perf_model.total_compiled_programs() == before, (
            "a replica-routed search compiled new programs"
        )
    finally:
        c.stop()


def test_shed_request_does_zero_device_work(tmp_path):
    """Admission shedding happens before the microbatcher and the
    engine: a 429'd request launches zero dispatches and compiles
    nothing — the whole point of shedding at the door."""
    import threading as _threading
    import time as _time

    from vearch_tpu.cluster import rpc
    from vearch_tpu.cluster.standalone import StandaloneCluster
    from vearch_tpu.sdk.client import VearchClient

    d = 16
    c = StandaloneCluster(
        data_dir=str(tmp_path / "c"), n_ps=1,
        ps_kwargs={"max_concurrent_searches": 1})
    c.start()
    try:
        cl = VearchClient(c.router_addr)
        cl.create_database("db")
        cl.create_space("db", {
            "name": "s", "partition_num": 1,
            "fields": [
                {"name": "v", "data_type": "vector", "dimension": d,
                 "index": {"index_type": "FLAT", "metric_type": "L2",
                           "params": {}}},
            ],
        })
        rng = np.random.default_rng(22)
        vecs = rng.standard_normal((80, d)).astype(np.float32)
        cl.upsert("db", "s", [{"_id": f"d{i}", "v": vecs[i]}
                              for i in range(80)])

        def search():
            q = rng.standard_normal(d).astype(np.float32)
            return rpc.call(c.router_addr, "POST", "/document/search", {
                "db_name": "db", "space_name": "s",
                "vectors": [{"field": "v", "feature": q.tolist()}],
                "limit": 5,
            })

        search()  # settle first-use programs
        ps = c.ps_nodes[0]
        pid = next(iter(ps.engines))
        rpc.call(ps.addr, "POST", "/ps/engine/config", {
            "partition_id": pid,
            "config": {"admission_queue_limit": 1,
                       "debug_search_delay_ms": 2000},
        })
        threads = [_threading.Thread(target=search) for _ in range(2)]
        try:
            for t in threads:
                t.start()
            deadline = _time.monotonic() + 5.0
            while ps._admission.waiting < 1:
                assert _time.monotonic() < deadline
                _time.sleep(0.01)
            # gate holder is pinned in its 2s injected wait and the
            # admission slot is full: the shed below resolves while
            # both are still parked, so the armed ledger can only see
            # the shed request itself
            before = perf_model.total_compiled_programs()
            ledger = perf_model.PerfLedger()
            ivf_ops.set_dispatch_ledger(ledger)
            try:
                with pytest.raises(rpc.RpcError) as ei:
                    search()
            finally:
                ivf_ops.set_dispatch_ledger(None)
            assert ei.value.code == 429
            assert ledger.tags == [], (
                f"a shed request reached the device: {ledger.tags}"
            )
            assert perf_model.total_compiled_programs() == before
        finally:
            for t in threads:
                t.join(timeout=15.0)
            rpc.call(ps.addr, "POST", "/ps/engine/config", {
                "partition_id": pid,
                "config": {"admission_queue_limit": 0,
                           "debug_search_delay_ms": 0},
            })
    finally:
        c.stop()


# -- gate 3: bytes materialized ----------------------------------------------


def test_full_scan_materializes_score_matrix():
    """At the headline serving shape (1M x 128, b=1024) the XLA scan
    materializes a 4 GB [B, N] f32 score matrix (the chip's compiler
    reports ONE such buffer as temp — tests/test_chip_compile.py) and
    streams the int8 mirror exactly once."""
    b, n_pad, d = 1024, 1_000_448, 128
    assert perf_model.scan_peak_bytes(b, n_pad) == b * n_pad * 4
    assert perf_model.scan_traffic_bytes(n_pad, d) == n_pad * d


def _topk_widths(jaxpr):
    """Columns of the operand of every `top_k` of a jaxpr, nested ones
    included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "top_k":
            yield eqn.invars[0].aval.shape[-1]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _topk_widths(sub)


def test_blockmax_selection_matches_kernel_constants():
    # mirrors ops/ivf.py _select_topk: r blocks of BLOCK (128) scores
    # gathered, never more blocks than exist; from r = GROUP_MIN_R on
    # their r * 128 scores are selected from in groups of GROUP (8), so
    # the widest sort after the gather takes the 16 r group maxima:
    # 4,096 at the benchmark's rerank 256, 8,192 at the three-stage
    # program's r0 = 512, not 32,768 and 65,536
    assert perf_model.BLOCK == ivf_ops.BLOCK == 128
    assert (ivf_ops.GROUP, ivf_ops.GROUP_MIN_R) == (8, 64)
    assert perf_model.blockmax_selected_blocks(128, 1_000_448) == 128
    assert perf_model.blockmax_selected_blocks(256, 1_000_448) \
        * perf_model.BLOCK == 32_768
    assert perf_model.blockmax_selected_blocks(128, 2048) == 16
    assert perf_model.select_width(256, 1_000_448) == 7_816  # N / 128
    assert perf_model.select_width(256, 1_000_064) == 7_813
    assert perf_model.select_width(512, 500_224) == 8_192  # 16 r0
    assert perf_model.select_width(512, 1_000_448) == 8_192
    assert perf_model.select_width(256, 262_144) == 4_096  # 16 r
    assert perf_model.select_width(256, 8_192) == 8_192  # the plain row
    # the model follows the code: the widest `top_k` operand of the
    # traced selection, on either side of every rule it has
    for r, n_pad in [(256, 1_000_448), (512, 500_224), (512, 262_144),
                     (512, 262_016), (128, 65_536), (128, 65_408),
                     (128, 65_536 + 40), (64, 65_536), (16, 131_072),
                     (132, 131_072), (100, 131_072), (300, 200)]:
        jaxpr = jax.make_jaxpr(
            lambda x, r=r: ivf_ops._select_topk(x, r))(
                jax.ShapeDtypeStruct((8, n_pad), jnp.float32))
        assert max(_topk_widths(jaxpr.jaxpr)) \
            == perf_model.select_width(r, n_pad), (r, n_pad)


# -- gate 4: HBM footprint model ---------------------------------------------


def test_footprint_tracks_published_device_state():
    # fresh engine: nothing probe-published yet, so the probe-state
    # growth below is observable
    eng, vecs = _build("IVFPQ", IVFPQ_PARAMS)
    idx = eng.indexes["emb"]
    store = eng.vector_stores["emb"]
    fp = idx.device_footprint_bytes()
    raw = perf_model.raw_store_footprint_bytes(
        store.capacity, store.dimension, store.store_dtype.itemsize)
    mirror = idx._mirror.device_bytes()
    # the model covers at least the raw rerank buffer + the int8 mirror
    assert fp >= raw + mirror
    assert mirror == perf_model.mirror_footprint_bytes(
        idx._mirror._h8.shape[0], D, "int8")
    # probe publish adds the bucket tensors to the model
    _search(eng, vecs, index_params={"scan_mode": "probe"})
    assert idx.device_footprint_bytes() > fp


def test_flat_footprint_is_store_only():
    eng, _ = _build("FLAT", {}, n=500)
    store = eng.vector_stores["emb"]
    assert eng.indexes["emb"].device_footprint_bytes() == (
        perf_model.raw_store_footprint_bytes(
            store.capacity, store.dimension, store.store_dtype.itemsize))


# -- progressive three-stage refinement (IVFRABITQ) --------------------------

RABITQ_PARAMS = {
    "ncentroids": 16, "train_iters": 4, "training_threshold": 256,
    # single-device ledger gates (the mesh three-stage program has its
    # own documented-dispatch gate in test_mesh_serving.py)
    "mesh_serving": "off",
}


@pytest.fixture(scope="module")
def rabitq_engine():
    return _build("IVFRABITQ", RABITQ_PARAMS, warmup=[8])


def test_three_stage_fused_documented_dispatch(rabitq_engine):
    """The RAM-store three-stage search (binary scan -> int8 rescore ->
    exact rerank) is ONE fused program; stage0=off falls back to the
    documented int8-only fused chain."""
    eng, vecs = rabitq_engine
    doc = perf_model.DOCUMENTED_DISPATCHES
    assert _search(eng, vecs).tags == doc["ivfrabitq_three_stage"]
    assert _search(eng, vecs, index_params={"stage0": "off"}).tags == \
        doc["ivfpq_full_fused"]


def test_three_stage_disk_documented_dispatch(tmp_path):
    """Against a disk store the chain splits exactly once: stages 0-1 on
    device, stage-2 through the host readahead gather — two dispatches,
    never a third."""
    schema = TableSchema("t", [
        FieldSchema("emb", DataType.VECTOR, dimension=D,
                    index=IndexParams("IVFRABITQ", MetricType.L2,
                                      {**RABITQ_PARAMS,
                                       "store_type": "RocksDB"})),
    ])
    eng = Engine(schema, data_dir=str(tmp_path / "d"))
    rng = np.random.default_rng(33)
    vecs = rng.standard_normal((N, D), dtype=np.float32)
    eng.upsert([{"_id": f"d{i:05d}", "emb": vecs[i]} for i in range(N)])
    eng.build_index()
    eng.wait_for_index()
    assert _search(eng, vecs).tags == \
        perf_model.DOCUMENTED_DISPATCHES["ivfrabitq_three_stage_disk"]
    eng.close()


def test_three_stage_warmed_zero_new_programs(rabitq_engine):
    """Warmed three-stage searches — including runtime-tuned r0/r1 once
    their shapes are traced — add ZERO compiled programs."""
    eng, vecs = rabitq_engine
    tuned = {"r0": 512, "r1": 64}
    _search(eng, vecs, b=8)              # settle first-use programs
    _search(eng, vecs, b=8, index_params=tuned)
    before = perf_model.total_compiled_programs()
    for _ in range(3):
        _search(eng, vecs, b=8)
        _search(eng, vecs, b=8, index_params=tuned)
    assert perf_model.total_compiled_programs() == before, (
        "warmed three-stage searches retrace per request")


def test_binary_footprint_model_and_density_gate(rabitq_engine):
    """Acceptance gate: the stage-0 bit planes cost <= 1/8 of the int8
    mirror's row payload for the same capacity, the perf model and the
    live device buffers agree byte-for-byte, and the per-row totals
    (payload + 8B scale/vsq aux) match the documented formulas."""
    eng, _ = rabitq_engine
    idx = eng.indexes["emb"]
    cap = idx._bits._h8.shape[0]
    assert cap == idx._mirror._h8.shape[0]  # tiers grow in lockstep
    # model-level density gate: 8x plane payload within the mirror total
    assert 8 * perf_model.binary_plane_bytes(cap, D) <= \
        perf_model.mirror_footprint_bytes(cap, D)
    # model == ledger == live device buffers (the sampler's ground truth)
    assert idx._bits.device_bytes() == \
        perf_model.binary_footprint_bytes(cap, D)
    planes, scale, vsq = idx._bits.flush()
    live = planes.nbytes + scale.nbytes + vsq.nbytes
    assert live == idx._bits.device_bytes(), (live, idx._bits.device_bytes())
    # and the footprint model exposes the stage-0 tier to the HBM gauges
    assert idx.device_footprint_bytes() >= (
        idx._mirror.device_bytes() + idx._bits.device_bytes())


def test_refine_depth_auto_defaults():
    """refine_depths is the documented auto-tuning: r1 covers the exact
    rerank budget (10k floor 128), r0 gives the int8 stage ~3.2x head
    room, both clamped to the corpus."""
    r0, r1 = perf_model.refine_depths(10, 1_000_000)
    assert r1 == 128 and r0 == 512
    r0, r1 = perf_model.refine_depths(100, 1_000_000)
    assert r1 == 1000 and r0 == 3200
    r0, r1 = perf_model.refine_depths(10, 300)   # tiny corpus clamps r0
    assert r1 == 128 and r0 == 300
    r0, r1 = perf_model.refine_depths(10, 64)    # r1 clamps too
    assert r1 == 64 and r0 == 64
    assert r0 >= r1


# -- gate 5: bytes over PCIe (tiered storage, PERF.md Tier 6) ----------------
#
# Disk-tier searches page bucket slabs HBM<-RAM<-NVMe; the PCIe ledger
# (perf_model.note_h2d_bytes / h2d_bytes_total) records every upload.
# The gates: cold misses move EXACTLY the modeled slab bytes, a warmed
# hot working set launches ZERO H2D bytes and ZERO new compiled
# programs, a repeating probe sequence converges onto pinned or
# prefetch-confirmed slabs, and the tiering machinery never changes
# results (bit-identical with prefetch on or off).


def _build_disk(tmp_path, name, n=8000, nlist=64, **params):
    from vearch_tpu.engine.disk_vector import DiskRawVectorStore
    from vearch_tpu.index.registry import create_index

    # uniform vectors -> near-balanced buckets, so the slab cap (and
    # with it the slot count under a 1 MB budget) is deterministic-ish;
    # recall quality is test_disk_index.py's business, not this file's
    rng = np.random.default_rng(7)
    base = rng.standard_normal((n, D)).astype(np.float32)
    store = DiskRawVectorStore(D, str(tmp_path / name))
    store.add(base)
    p = IndexParams(
        index_type="DISKANN",
        params={"ncentroids": nlist, "nprobe": 8, **params},
    )
    idx = create_index(p, store)
    idx.train(base)
    idx.absorb(store.count)
    return base, idx


def test_tier_cold_misses_move_exactly_modeled_bytes(tmp_path):
    base, idx = _build_disk(tmp_path, "cold", cache_mb=1, ram_mb=8,
                            prefetch=False)
    try:
        q = base[:8]
        b0 = perf_model.h2d_bytes_total()
        idx.search(q, 10, None)
        cache = idx._cache
        st = cache.stats()
        assert st["misses"] > 0
        assert perf_model.h2d_bytes_total() - b0 == (
            perf_model.tier_h2d_bytes(st["misses"], cache.cap, D)
        ), "cold-path H2D must match the slab model byte-for-byte"
        assert st["h2d_bytes"] == perf_model.h2d_bytes_total() - b0
    finally:
        idx.close()


def test_tier_warmed_hot_set_zero_h2d_zero_retrace(tmp_path):
    """THE steady-state gate: once the hot working set is resident and
    pinned, a repeat search launches zero H2D bytes and zero new
    compiled programs — the scan runs entirely from HBM."""
    base, idx = _build_disk(tmp_path, "warm", cache_mb=1, ram_mb=8)
    try:
        q = base[:8]
        for _ in range(12):  # warm + let pins form
            idx.search(q, 10, None)
        idx._prefetcher.drain()
        b0 = perf_model.h2d_bytes_total()
        c0 = perf_model.total_compiled_programs()
        s0, i0 = idx.search(q, 10, None)
        idx._prefetcher.drain()
        assert perf_model.h2d_bytes_total() - b0 == 0, (
            "warmed hot-path search moved bytes over PCIe"
        )
        assert perf_model.total_compiled_programs() - c0 == 0, (
            "warmed hot-path search compiled a new program"
        )
        st = idx._cache.stats()
        assert st["pinned"] > 0  # the hot buckets actually pinned
        # and the warmed path returns exactly the cold-path results
        s1, i1 = idx.search(q, 10, None)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(s0, s1)
    finally:
        idx.close()


def test_tier_repeating_sequence_converges_on_pins_and_prefetch(tmp_path):
    """A repeating probe sequence must converge to >=90% of lookups
    landing on pinned or prefetch-confirmed slabs (the acceptance
    floor): the predictor learns the alternation and the pin set
    absorbs the stable half."""
    base, idx = _build_disk(tmp_path, "conv", n=20000, nlist=64,
                            cache_mb=1, ram_mb=32)
    try:
        qa, qb = base[:4], base[10000:10004]
        for _ in range(30):
            idx.search(qa, 10, None)
            idx._prefetcher.drain()
            idx.search(qb, 10, None)
            idx._prefetcher.drain()
        st = idx._cache.stats()
        lookups = st["hits"] + st["misses"]
        served = st["pin_hits"] + st["prefetch_hits"]
        assert lookups > 0
        assert served / lookups >= 0.9, (
            f"pin+prefetch hit share {served}/{lookups} below 90%"
        )
        pf = idx._prefetcher.stats()
        assert pf["errors"] == 0
    finally:
        idx.close()


def test_tier_prefetch_is_bit_identical(tmp_path):
    base, on = _build_disk(tmp_path, "on", prefetch=True, cache_mb=1)
    _, off = _build_disk(tmp_path, "off", prefetch=False, cache_mb=1)
    try:
        q = base[:16]
        for _ in range(3):
            s_on, i_on = on.search(q, 10, None)
            on._prefetcher.drain()
            s_off, i_off = off.search(q, 10, None)
            np.testing.assert_array_equal(i_on, i_off)
            np.testing.assert_array_equal(s_on, s_off)
    finally:
        on.close()
        off.close()


def test_tier_multipass_matches_single_pass(tmp_path):
    """When the probe set exceeds the HBM slots the search degrades to
    several fixed-shape passes — same ids, same scores, no ValueError
    (the graceful-degradation satellite)."""
    base, small = _build_disk(tmp_path, "mp_small", n=20000, nlist=256,
                              cache_mb=1, prefetch=False)
    _, big = _build_disk(tmp_path, "mp_big", n=20000, nlist=256,
                         cache_mb=512, prefetch=False)
    try:
        q = base[:8]
        p = {"nprobe": 256}
        groups = small._ensure_cache().plan_passes(
            np.arange(256).reshape(1, -1))
        assert len(groups) > 1  # the probe set genuinely overflows
        s_m, i_m = small.search(q, 10, None, p)
        s_1, i_1 = big.search(q, 10, None, p)
        np.testing.assert_array_equal(i_m, i_1)
        np.testing.assert_allclose(s_m, s_1, rtol=1e-5, atol=1e-5)
    finally:
        small.close()
        big.close()
