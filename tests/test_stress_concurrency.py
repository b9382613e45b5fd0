"""Concurrency stress: writers + searchers + deleters hammering one
engine (the parity answer to TSAN-style CI the reference lacks too —
SURVEY §5 race detection)."""

import os
import threading

import numpy as np

from vearch_tpu.engine.engine import Engine, SearchRequest
from vearch_tpu.engine.types import (
    DataType, FieldSchema, IndexParams, MetricType, TableSchema,
)

D = 16

PKG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "vearch_tpu")

_STATIC_LOCK_GRAPH = None


def _assert_static_covers(edges):
    """ISSUE 20 truth link: every (first, then) acquisition edge the
    runtime lockcheck recorder observed must be covered by the static
    lock-order graph (`lint --lock-graph`). A runtime edge the
    analyzer cannot see is a resolution blind spot to fix — the
    static cycle-freedom proof only binds if the runtime behavior is
    inside the proved graph. Computed in-process once per session."""
    global _STATIC_LOCK_GRAPH
    from vearch_tpu.tools.lint import callgraph
    from vearch_tpu.tools.lint.core import run_paths

    if _STATIC_LOCK_GRAPH is None:
        run_paths([PKG])  # builds callgraph.LAST as a side effect
        assert callgraph.LAST is not None
        _STATIC_LOCK_GRAPH = callgraph.LAST.lock_graph_artifact()
    assert _STATIC_LOCK_GRAPH["cycles"] == []
    uncovered = sorted(
        (a, b) for (a, b) in edges
        if not callgraph.edge_covered(_STATIC_LOCK_GRAPH, a, b))
    assert not uncovered, (
        "runtime acquisition edges missing from the static lock-order "
        f"graph (analyzer blind spot): {uncovered}")


def test_concurrent_upsert_search_delete(rng):
    schema = TableSchema(
        "stress",
        fields=[FieldSchema("v", DataType.VECTOR, dimension=D,
                            index=IndexParams("IVFFLAT", MetricType.L2,
                                              {"ncentroids": 8,
                                               "training_threshold": 300}))],
        refresh_interval_ms=30,
    )
    eng = Engine(schema)
    eng.start_refresh_loop()
    vecs = rng.standard_normal((3000, D)).astype(np.float32)
    eng.upsert([{"_id": f"seed{i}", "v": vecs[i]} for i in range(400)])
    eng.wait_for_index(timeout=120)

    errors: list[Exception] = []
    stop = threading.Event()

    def writer(tid: int):
        try:
            for batch in range(8):
                base = 400 + tid * 800 + batch * 100
                eng.upsert([
                    {"_id": f"w{tid}_{base + i}", "v": vecs[(base + i) % 3000]}
                    for i in range(100)
                ])
        except Exception as e:
            errors.append(e)

    def searcher():
        try:
            while not stop.is_set():
                res = eng.search(SearchRequest(vectors={"v": vecs[:4]}, k=5))
                assert len(res) == 4
        except Exception as e:
            errors.append(e)

    def deleter():
        try:
            for i in range(50):
                eng.delete([f"seed{i}"])
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(3)]
    threads += [threading.Thread(target=searcher) for _ in range(2)]
    threads += [threading.Thread(target=deleter)]
    for t in threads:
        t.start()
    for t in threads[:3] + threads[-1:]:
        t.join(timeout=180)
    stop.set()
    for t in threads[3:5]:
        t.join(timeout=60)

    assert not errors, errors
    # final state is consistent: 400 seeds - 50 deleted + 3*800 writes
    assert eng.doc_count == 400 - 50 + 3 * 8 * 100
    # absorb everything and verify no duplicate docids in the index
    idx = eng.indexes["v"]
    idx.absorb(eng.vector_stores["v"].count)
    all_members = [m for mm in idx._members for m in mm]
    assert len(all_members) == len(set(all_members)), "duplicate absorb"
    # searches see post-stress writes
    res = eng.search(SearchRequest(vectors={"v": vecs[400:401]}, k=3))
    assert res[0].items
    eng.close()


def test_ivfflat_mask_and_table_are_of_one_generation(rng, monkeypatch):
    """Four searching threads beside a writer and a deleter: every
    dispatch scans (rows, norms, ids, slot-major mask) of ONE published
    table. The writer forces a publish a batch (a new table, often a
    new `cap`), the deleter a new mask on the table that stands; a
    search that paired one generation's mask with another's ids would
    let padding through, or hide a row that is alive. Checked on every
    dispatch, on what the program was handed, and on every answer."""
    from vearch_tpu.ops import ivf as ivf_ops

    schema = TableSchema(
        "stress_mask",
        fields=[FieldSchema("v", DataType.VECTOR, dimension=D,
                            index=IndexParams("IVFFLAT", MetricType.L2,
                                              {"ncentroids": 8, "nprobe": 8,
                                               "training_threshold": 300}))],
        refresh_interval_ms=30,
    )
    eng = Engine(schema)
    # the four threads INSIDE the index at once, as a PS's handler
    # threads are with 64-row requests: the scheduler's one dispatcher
    # thread would run them one after another
    eng.micro_batch = False
    eng.start_refresh_loop()
    vecs = rng.standard_normal((3000, D)).astype(np.float32)
    eng.upsert([{"_id": f"seed{i}", "v": vecs[i]} for i in range(400)])
    eng.wait_for_index(timeout=120)
    stable = np.arange(100, 400)  # docids nobody deletes

    errors: list[Exception] = []
    dispatches = []
    scan = ivf_ops.ivfflat_candidates

    def checked_scan(q, cents, bucket_vecs, sqnorm, bucket_ids, bucket_ok,
                     *args, **kw):
        try:
            ids, ok = np.asarray(bucket_ids), np.asarray(bucket_ok)
            assert ids.shape == ok.shape == bucket_vecs.shape[:2]
            assert not ok[ids < 0].any(), "padding let through"
            held = np.isin(ids, stable)
            assert held.sum() == stable.size and ok[held].all(), (
                "an alive row masked")
            dispatches.append(ids.shape[1])
        except Exception as e:
            errors.append(e)
        return scan(q, cents, bucket_vecs, sqnorm, bucket_ids, bucket_ok,
                    *args, **kw)

    monkeypatch.setattr(ivf_ops, "ivfflat_candidates", checked_scan)
    stop = threading.Event()

    def paced(steps):
        """Each step after the searchers got two more dispatches in (a
        first search compiles for seconds: unpaced, the writer is done
        before it returns)."""
        for step in steps:
            seen = len(dispatches)
            step()
            for _ in range(500):
                if len(dispatches) >= seen + 2 or errors:
                    break
                stop.wait(0.02)

    def writer():
        try:
            paced(lambda base=base: eng.upsert(
                [{"_id": f"w{base + i}", "v": vecs[base + i]}
                 for i in range(100)]) for base in range(400, 1600, 100))
        except Exception as e:
            errors.append(e)

    def deleter():
        try:
            paced(lambda lo=lo: eng.delete(
                [f"seed{i}" for i in range(lo, lo + 5)])
                for lo in range(0, 100, 5))
        except Exception as e:
            errors.append(e)

    def searcher(tid: int):
        try:
            mine = stable[tid::4][:8]
            while not stop.is_set():
                res = eng.search(SearchRequest(vectors={"v": vecs[mine]}, k=3))
                # every list is probed: a stored, alive row is its own
                # nearest neighbour in every answer
                assert [r.items[0].key for r in res] == [
                    f"seed{i}" for i in mine]
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=writer),
               threading.Thread(target=deleter)]
    threads += [threading.Thread(target=searcher, args=(t,))
                for t in range(4)]
    for t in threads:
        t.start()
    for t in threads[:2]:
        t.join(timeout=180)
    stop.set()
    for t in threads[2:]:
        t.join(timeout=60)
    assert not errors, errors[:3]
    assert dispatches
    info = eng.indexes["v"].ivf_info()
    assert info["publishes"] >= 2 and info["mask_builds"] >= info["publishes"]
    # what the writers and the deleter left is what a search now sees
    res = eng.search(SearchRequest(vectors={"v": vecs[[0, 99, 1599]]}, k=1))
    assert [r.items[0].key for r in res][2] == "w1599"
    assert not {"seed0", "seed99"} & {r.items[0].key for r in res}
    eng.close()


def test_cluster_stress_under_lockcheck(tmp_path, rng):
    """The same class of stress, but against the replicated cluster
    layer with VEARCH_LOCKCHECK enabled: every ps/raft/wal/querycache
    lock becomes a named DebugLock recording the acquisition graph,
    and `_guarded_by` writes are runtime-verified. Concurrent writes,
    searches, and a mid-stress flush must leave the recorder with zero
    violations — no lock-order inversion is *possible*, not merely
    unobserved, given the edges this run produced."""
    from vearch_tpu.cluster.master import MasterServer
    from vearch_tpu.cluster.ps import PSServer
    from vearch_tpu.cluster.router import RouterServer
    from vearch_tpu.sdk.client import VearchClient
    from vearch_tpu.tools import lockcheck

    lockcheck.reset()
    lockcheck.enable()  # BEFORE construction: locks are minted at init
    master = nodes = router = None
    try:
        master = MasterServer(heartbeat_ttl=3600.0)
        master.start()
        nodes = []
        for i in range(2):
            ps = PSServer(data_dir=str(tmp_path / f"ps{i}"),
                          master_addr=master.addr,
                          heartbeat_interval=0.3,
                          flush_interval=3600.0, raft_tick=0.3)
            ps.start()
            nodes.append(ps)
        router = RouterServer(master_addr=master.addr)
        router.start()

        cl = VearchClient(router.addr)
        cl.create_database("db")
        cl.create_space("db", {
            "name": "s", "partition_num": 2, "replica_num": 2,
            "fields": [{"name": "v", "data_type": "vector",
                        "dimension": D,
                        "index": {"index_type": "FLAT",
                                  "metric_type": "L2", "params": {}}}],
        })
        vecs = rng.standard_normal((400, D)).astype("float32")
        cl.upsert("db", "s", [{"_id": f"seed{i}", "v": vecs[i].tolist()}
                              for i in range(100)])

        errors: list[Exception] = []
        stop = threading.Event()

        def writer(tid: int):
            try:
                for b in range(4):
                    base = 100 + tid * 100 + b * 25
                    cl.upsert("db", "s", [
                        {"_id": f"w{tid}_{base + i}",
                         "v": vecs[(base + i) % 400].tolist()}
                        for i in range(25)
                    ])
            except Exception as e:
                errors.append(e)

        def searcher():
            try:
                while not stop.is_set():
                    out = cl.search("db", "s",
                                    [{"field": "v", "feature": vecs[:2]}],
                                    limit=3)
                    assert len(out) == 2
            except Exception as e:
                errors.append(e)

        def flusher():
            try:
                for ps in nodes:
                    for pid in list(ps.engines):
                        ps.flush_partition(pid)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(t,),
                                    daemon=True, name=f"stress-w{t}")
                   for t in range(2)]
        threads += [threading.Thread(target=searcher, daemon=True,
                                     name=f"stress-s{i}")
                    for i in range(2)]
        threads += [threading.Thread(target=flusher, daemon=True,
                                     name="stress-flush")]
        for t in threads:
            t.start()
        for t in threads[:2] + threads[-1:]:
            t.join(timeout=180)
        stop.set()
        for t in threads[2:4]:
            t.join(timeout=60)

        assert not errors, errors
        # the detector really ran: the instrumented layer produced
        # acquisition edges (e.g. ps._lock held while minting raft locks)
        edges = lockcheck.acquisition_edges()
        assert edges, "no DebugLock edges recorded — lockcheck inert?"
        lockcheck.check()  # zero inversions / unguarded writes / misuse
        _assert_static_covers(edges)
    finally:
        if router is not None:
            router.stop()
        for ps in (nodes or []):
            try:
                ps.stop(flush=False)
            except Exception:
                pass
        if master is not None:
            master.stop()
        lockcheck.reset()


def test_concurrent_split_under_lockcheck(tmp_path, rng):
    """An online partition split racing writers and searchers with the
    lock-discipline recorder on: the split machinery's new locks
    (ps._split_lock, the mirror condvar, the master's elastic-job and
    reconfig locks) must produce zero ordering violations while the
    full copy → mirror → sync → cutover pipeline runs to completion."""
    from vearch_tpu.cluster import rpc
    from vearch_tpu.cluster.master import MasterServer
    from vearch_tpu.cluster.ps import PSServer
    from vearch_tpu.cluster.router import RouterServer
    from vearch_tpu.sdk.client import VearchClient
    from vearch_tpu.tools import lockcheck

    lockcheck.reset()
    lockcheck.enable()  # BEFORE construction: locks are minted at init
    master = nodes = router = None
    try:
        master = MasterServer(heartbeat_ttl=3600.0)
        master.start()
        nodes = []
        for i in range(2):
            ps = PSServer(data_dir=str(tmp_path / f"ps{i}"),
                          master_addr=master.addr,
                          heartbeat_interval=0.3,
                          flush_interval=3600.0, raft_tick=0.3)
            ps.start()
            nodes.append(ps)
        router = RouterServer(master_addr=master.addr)
        router.start()

        cl = VearchClient(router.addr, master_addr=master.addr)
        cl.create_database("db")
        cl.create_space("db", {
            "name": "s", "partition_num": 1, "replica_num": 1,
            "fields": [{"name": "v", "data_type": "vector",
                        "dimension": D,
                        "index": {"index_type": "FLAT",
                                  "metric_type": "L2", "params": {}}}],
        })
        vecs = rng.standard_normal((400, D)).astype("float32")
        cl.upsert("db", "s", [{"_id": f"seed{i}", "v": vecs[i].tolist()}
                              for i in range(60)])
        parent = cl.get_space("db", "s")["partitions"][0]["id"]

        errors: list[Exception] = []
        stop = threading.Event()
        acked: list[str] = []

        def writer(tid: int):
            i = 0
            try:
                while not stop.is_set():
                    ids = [f"w{tid}_{i + j}" for j in range(5)]
                    cl.upsert("db", "s", [
                        {"_id": k, "v": vecs[(60 + i + j) % 400].tolist()}
                        for j, k in enumerate(ids)
                    ])
                    acked.extend(ids)
                    i += 5
            except Exception as e:
                errors.append(e)

        def searcher():
            try:
                while not stop.is_set():
                    out = cl.search("db", "s",
                                    [{"field": "v", "feature": vecs[:2]}],
                                    limit=3)
                    assert len(out) == 2
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(t,),
                                    daemon=True, name=f"split-w{t}")
                   for t in range(2)]
        threads += [threading.Thread(target=searcher, daemon=True,
                                     name="split-s0")]
        for t in threads:
            t.start()
        try:
            job = cl.split_partition("db", "s", parent, timeout_s=120.0)
            done = cl.wait_elastic_job(job["job_id"], timeout_s=150.0)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60)
        assert not errors, errors
        assert done["status"] == "done"
        # the split actually exercised the new machinery end to end
        kids = [p["id"]
                for p in cl.get_space("db", "s")["partitions"]]
        assert len(kids) == 2 and parent not in kids
        docs = cl.query("db", "s", limit=len(acked) + 200, fields=[])
        assert len(docs) == 60 + len(acked)

        edges = lockcheck.acquisition_edges()
        assert edges, "no DebugLock edges recorded — lockcheck inert?"
        lockcheck.check()  # zero inversions / unguarded writes / misuse
        _assert_static_covers(edges)
        # the health rollup is heartbeat-fed, so it drains within a
        # beat of the parent's retirement
        import time as _time
        for _ in range(50):
            if rpc.call(master.addr, "GET",
                        "/cluster/health")["splits_running"] == 0:
                break
            _time.sleep(0.1)
        else:
            raise AssertionError("splits_running never drained")
    finally:
        if router is not None:
            router.stop()
        for ps in (nodes or []):
            try:
                ps.stop(flush=False)
            except Exception:
                pass
        if master is not None:
            master.stop()
        lockcheck.reset()


def test_diskann_absorb_search_under_lockcheck(tmp_path, rng):
    """The narrowed disk-tier critical section, proven: a realtime
    writer (store.add + absorb) races searcher threads while the
    prefetch worker pages slabs in the background. Under
    VEARCH_LOCKCHECK every tiering lock (absorb, hbm_cache, ram tier,
    prefetch) is a named DebugLock — the run must leave a non-empty
    acquisition graph with zero violations, i.e. the absorb lock never
    nests with the cache locks in an invertible order."""
    from vearch_tpu.engine.disk_vector import DiskRawVectorStore
    from vearch_tpu.engine.types import IndexParams
    from vearch_tpu.index.registry import create_index
    from vearch_tpu.tools import lockcheck

    lockcheck.reset()
    lockcheck.enable()  # BEFORE construction: locks are minted at init
    idx = None
    try:
        base = rng.standard_normal((6000, D)).astype(np.float32)
        store = DiskRawVectorStore(D, str(tmp_path / "dstress"))
        store.add(base[:4000])
        p = IndexParams(
            index_type="DISKANN",
            params={"ncentroids": 16, "nprobe": 4, "cache_mb": 1,
                    "ram_mb": 8},
        )
        idx = create_index(p, store)
        idx.train(base[:4000])
        idx.absorb(store.count)

        errors: list[Exception] = []
        stop = threading.Event()

        def writer():
            try:
                for lo in range(4000, 6000, 200):
                    store.add(base[lo:lo + 200])
                    idx.absorb(store.count)
            except Exception as e:
                errors.append(e)
            finally:
                stop.set()

        def searcher(tid: int):
            try:
                q = base[tid * 8:tid * 8 + 4]
                while not stop.is_set():
                    s, ids = idx.search(q, 5, None)
                    assert ids.shape == (4, 5)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=writer, name="dstress-writer",
                                    daemon=True)]
        threads += [
            threading.Thread(target=searcher, args=(t,),
                             name=f"dstress-search{t}", daemon=True)
            for t in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        idx._prefetcher.drain()

        assert not errors, errors
        assert idx.indexed_count == 6000
        # the checker actually saw the tiering locks interact
        edges = lockcheck.acquisition_edges()
        assert edges, "lockcheck recorded no lock activity"
        lockcheck.check()  # raises listing any inversion / guarded write
        _assert_static_covers(edges)
    finally:
        if idx is not None:
            idx.close()
        lockcheck.reset()


def test_rabitq_absorb_binary_search_under_lockcheck(rng):
    """Concurrent absorb + three-stage binary search, proven: a
    realtime writer appends rows (store.add + absorb — which quantizes
    into BOTH compressed tiers, the int8 mirror and the stage-0 bit
    planes) while searcher threads run the fused binary -> int8 ->
    exact chain, whose flush() races the tail-append. Under
    VEARCH_LOCKCHECK every lock is a named DebugLock — the run must
    leave a non-empty acquisition graph with zero inversions."""
    from vearch_tpu.engine.raw_vector import RawVectorStore
    from vearch_tpu.index.registry import create_index
    from vearch_tpu.tools import lockcheck

    lockcheck.reset()
    lockcheck.enable()  # BEFORE construction: locks are minted at init
    try:
        base = rng.standard_normal((6000, D)).astype(np.float32)
        store = RawVectorStore(D)
        store.add(base[:4000])
        p = IndexParams(
            index_type="IVFRABITQ", metric_type=MetricType.L2,
            params={"ncentroids": 16, "train_iters": 4,
                    "mesh_serving": "off"},
        )
        idx = create_index(p, store)
        idx.train(base[:4000])
        idx.absorb(store.count)

        errors: list[Exception] = []
        stop = threading.Event()

        def writer():
            try:
                for lo in range(4000, 6000, 200):
                    store.add(base[lo:lo + 200])
                    idx.absorb(store.count)
            except Exception as e:
                errors.append(e)
            finally:
                stop.set()

        def searcher(tid: int):
            try:
                q = base[tid * 8:tid * 8 + 4]
                while not stop.is_set():
                    s, ids = idx.search(q, 5, None)
                    assert ids.shape == (4, 5)
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=writer, name="rq-writer",
                                    daemon=True)]
        threads += [
            threading.Thread(target=searcher, args=(t,),
                             name=f"rq-search{t}", daemon=True)
            for t in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)

        assert not errors, errors
        assert idx.indexed_count == 6000
        # both compressed tiers absorbed every row in lockstep
        assert idx._bits._n == idx._mirror._n == 6000
        edges = lockcheck.acquisition_edges()
        assert edges, "lockcheck recorded no lock activity"
        lockcheck.check()  # raises listing any inversion / guarded write
        _assert_static_covers(edges)
    finally:
        lockcheck.reset()


def test_concurrent_first_placement_of_raw_store():
    """Searchers racing the FIRST device placement of a raw store (two
    request threads on a cold engine; the shadow-recall sampler beside
    a mesh partition's first single-device request — how the
    four-device chip_smoke phase found it): before the placement lock a
    second caller saw `_device` set with `_device_sqnorm` still None and
    crashed, or tail-flushed into a half-built buffer. Every caller
    must get the whole buffer and the sqnorm column that belongs to it."""
    import sys

    from vearch_tpu.engine.raw_vector import RawVectorStore
    from vearch_tpu.ops.distance import host_sqnorms

    rows = np.random.default_rng(3).standard_normal(
        (40_000, 64)).astype(np.float32)
    workers = 4 * (os.cpu_count() or 2)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for _ in range(3):
            store = RawVectorStore(64)
            store.add(rows)
            barrier = threading.Barrier(workers)
            out, errs = [], []

            def place():
                try:
                    barrier.wait(timeout=30)
                    out.append(store.device_buffer())
                except Exception as e:  # noqa: BLE001 — the assertion
                    errs.append(e)

            threads = [threading.Thread(target=place)
                       for _ in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            assert not errs, errs
            assert len(out) == workers
            want_sq = host_sqnorms(rows)
            for base, sqn, n in out:
                assert n == rows.shape[0]
                np.testing.assert_array_equal(np.asarray(base)[:n], rows)
                np.testing.assert_array_equal(np.asarray(sqn)[:n], want_sq)
    finally:
        sys.setswitchinterval(old)
