"""Headline benchmark: SIFT1M-scale IVFPQ search QPS on TPU.

Config mirrors BASELINE.json's north-star row: 1M x 128, IVFPQ
nlist=2048 m=32 nbits=8, batched queries, recall@10 target >= 0.95
(verified against an exact scan each run; the run fails the recall gate
rather than report a fast-but-wrong number).

vs_baseline = TPU QPS / CPU QPS, where the CPU baseline is the strongest
IVFPQ ADC scan this image allows (no faiss is installed): a vectorised
batched-LUT numpy ADC (LUTs for all probed lists computed in one einsum,
codes gathered in one indexed read) over the *same* trained structures,
run across ALL host cores via multiprocessing. Both the single-process
and the all-cores number are printed in the stderr diag along with the
core count; vs_baseline divides by the parallel (larger) one. The
reference engine's scan is the same ADC algorithm (OpenMP + AVX,
/root/reference/internal/engine/index/impl/gamma_index_ivfpq.cc).

Prints exactly one JSON line:
    {"metric": ..., "value": ..., "unit": "qps", "vs_baseline": ...}
"""

import json
import multiprocessing
import os
import sys
import time

import numpy as np


def _capacity_mode() -> bool:
    return os.environ.get("VEARCH_BENCH_CAPACITY", "").lower() in (
        "1", "true", "yes", "on"
    )


def _metric_name(batch: int) -> str:
    if _capacity_mode():
        return f"ivfpq_16M_capacity_search_qps_b{batch}_r@10>=0.95"
    return "ivfpq_sift1m_like_search_qps_b1024_r@10>=0.95"


def _emit_error(msg: str) -> None:
    print(json.dumps({
        "metric": _metric_name(64 if _capacity_mode() else 1024),
        "value": 0,
        "unit": "qps",
        "vs_baseline": 0,
        "error": msg,
    }))


def _require_device() -> None:
    """Fail in seconds, with the backend's own error, unless JAX finds
    a TPU. Checked in-process: this process is the chip's one owner, and
    a probing child would hold the chip against it."""
    import jax

    devs = jax.devices()  # raises the backend's error when init fails
    print(f"devices: {[str(d) for d in devs]}", file=sys.stderr, flush=True)
    if devs[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures on a TPU; jax found {devs[0].platform!r} "
            f"({devs[0].device_kind}). VEARCH_BENCH_DRYRUN=1 runs the "
            f"pipeline at toy size on the CPU.")


def build_data(n=1_000_000, d=128, seed=0):
    rng = np.random.default_rng(seed)
    nc = 5000
    centers = (rng.standard_normal((nc, d)) * 3).astype(np.float32)
    which = rng.integers(0, nc, n)
    base = centers[which] + 0.7 * rng.standard_normal((n, d)).astype(np.float32)
    q_idx = rng.choice(n, 1024, replace=False)
    queries = base[q_idx] + 0.1 * rng.standard_normal((1024, d)).astype(np.float32)
    return base, queries


# --- CPU baseline -----------------------------------------------------------
# Worker state is inherited over fork (Linux default start method); the
# arrays are read-only in the workers so no copies are made.
_CPU_STATE = {}


def _cpu_init_state(index):
    cents = np.asarray(index.centroids, dtype=np.float32)
    cb = np.asarray(index.codebooks, dtype=np.float32)  # [m, ksub, dsub]
    _CPU_STATE.update(
        cents=cents,
        cents_sq=(cents ** 2).sum(1),
        cb=cb,
        cb_sq=(cb ** 2).sum(-1),  # [m, ksub]
        codes=index._codes[: index.indexed_count],
        members=[np.asarray(mm, dtype=np.int64) for mm in index._members],
    )


def _cpu_adc_chunk(args):
    """Batched-LUT ADC over a chunk of queries.

    Per query: one matmul for coarse assign, ONE einsum building the LUTs
    of all nprobe lists at once, one fancy-indexed gather over the
    concatenated candidate codes. This is the vectorised formulation the
    reference's OpenMP scan implements per-thread
    (gamma_index_ivfpq.cc scan_list_with_table).
    """
    qs, nprobe, k = args
    s = _CPU_STATE
    cents, cents_sq = s["cents"], s["cents_sq"]
    cb, cb_sq = s["cb"], s["cb_sq"]
    codes, members = s["codes"], s["members"]
    m, ksub, dsub = cb.shape
    marange = np.arange(m)[None, :]
    out = []
    for q in qs:
        d2c = cents_sq - 2.0 * (cents @ q)
        probes = np.argpartition(d2c, nprobe)[:nprobe]
        lists = [members[c] for c in probes]
        sizes = np.array([l.size for l in lists])
        if sizes.sum() == 0:  # every probed list empty: nothing to rank
            out.append(np.empty(0, dtype=np.int64))
            continue
        ids = np.concatenate(lists)
        seg = np.repeat(np.arange(len(probes)), sizes)
        resid = (q[None, :] - cents[probes]).reshape(len(probes), m, dsub)
        luts = (cb_sq[None] - 2.0 * np.einsum("pmd,mkd->pmk", resid, cb)
                + (resid ** 2).sum(-1)[:, :, None])  # [p, m, ksub]
        cc = codes[ids]  # [n, m]
        dist = luts[seg[:, None], marange, cc].sum(1)
        top = ids[np.argpartition(dist, min(k, dist.size - 1))[:k]]
        out.append(top)
    return out


def cpu_ivfpq_qps(index, queries, nprobe=32, n_queries=32, k=10):
    """Strongest CPU ADC run this image allows: vectorised batched-LUT
    scan, single-process AND fanned across all host cores. Returns
    (best_qps, diag-dict); vs_baseline divides by best_qps."""
    _cpu_init_state(index)
    qs = queries[:n_queries].astype(np.float32)

    _cpu_adc_chunk((qs[:2], nprobe, k))  # warm caches
    t0 = time.time()
    _cpu_adc_chunk((qs, nprobe, k))
    qps_1p = n_queries / (time.time() - t0)

    ncores = os.cpu_count() or 1
    qps_mp = 0.0
    if ncores > 1:
        # fork happens AFTER jax/TPU-runtime threads exist, so a child
        # can deadlock on a mutex caught mid-fork — bound every pool op
        # so a wedged child costs minutes, not the whole bench run
        chunks = [(c, nprobe, k) for c in np.array_split(qs, ncores) if len(c)]
        pool = multiprocessing.Pool(ncores)
        try:
            pool.map_async(
                _cpu_adc_chunk, [(qs[:1], nprobe, k)] * ncores
            ).get(timeout=120)  # warm
            t0 = time.time()
            pool.map_async(_cpu_adc_chunk, chunks).get(timeout=600)
            qps_mp = n_queries / (time.time() - t0)
        except multiprocessing.TimeoutError:
            print("parallel CPU baseline timed out; using single-process",
                  file=sys.stderr, flush=True)
        finally:
            pool.terminate()
            pool.join()
    best = max(qps_1p, qps_mp)
    return best, {
        "cpu_baseline_qps": round(best, 1),
        "cpu_qps_1proc": round(qps_1p, 1),
        "cpu_qps_allcores": round(qps_mp, 1),
        "cpu_ncores": ncores,
        "cpu_method": f"numpy batched-LUT ADC, nprobe={nprobe}, "
                      "multiprocess over all cores; baseline = max",
    }


def _dryrun() -> bool:
    """VEARCH_BENCH_DRYRUN=1: run the FULL bench pipeline at toy scale
    on CPU — no TPU check, no meaningful numbers. Exists so bench-code
    regressions surface before a chip run is spent on them."""
    return os.environ.get("VEARCH_BENCH_DRYRUN", "").lower() in (
        "1", "true", "yes", "on"
    )


# --- resumability ------------------------------------------------------------
# The trained engine + query set persist under VEARCH_BENCH_CACHE
# (default ./.bench_cache) so a retry reloads them (training skipped;
# raw vectors are re-absorbed), and every phase appends a partial-result
# line to disk the moment it completes — a run that dies mid-way still
# leaves per-phase numbers behind.


def _cache_dir() -> str:
    return os.environ.get(
        "VEARCH_BENCH_CACHE",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     ".bench_cache"),
    )


def _phase_emitter(cache_key: str):
    """(emit, path): emit(phase, **kv) prints one JSON line to stderr
    AND appends it to the partials file, so partial results survive a
    run that dies mid-way."""
    os.makedirs(_cache_dir(), exist_ok=True)
    path = os.path.join(_cache_dir(), f"partial_{cache_key}.jsonl")

    def emit(phase: str, **kv):
        rec = {"phase": phase, "t_s": round(time.time(), 2), **kv}
        line = json.dumps(rec)
        print(line, file=sys.stderr, flush=True)
        try:
            with open(path, "a") as f:
                f.write(line + "\n")
        except OSError:
            pass  # partials are best-effort; never kill the bench

    return emit, path


def _phase_cached(partial_path: str, phase: str):
    """Last completed partial record for ``phase``, or None. Lets an
    expensive phase skip recompute on a resumed run — the partials file
    IS the resume state."""
    try:
        with open(partial_path) as f:
            recs = [json.loads(ln) for ln in f if ln.strip()]
    except (OSError, ValueError):
        return None
    hits = [r for r in recs if r.get("phase") == phase
            and "error" not in r]
    if not hits:
        return None
    return {k: v for k, v in hits[-1].items() if k not in ("phase", "t_s")}


def tail_latency_bench(dry: bool) -> dict:
    """Merged-search tail quantiles under an injected straggler,
    hedging ON vs OFF (tail-latency tentpole). Runs an in-process
    3-PS replica-3 cluster: the partition leader gets a killable
    per-search delay of ~10x the observed median, then the same query
    stream is measured through a hedging router and a hedging-disabled
    router. The headline is the p99 ratio — and the hedge hit-rate
    says how much extra traffic bought it."""
    import tempfile

    from vearch_tpu.cluster import rpc as _rpc
    from vearch_tpu.cluster.router import RouterServer
    from vearch_tpu.cluster.standalone import StandaloneCluster
    from vearch_tpu.sdk.client import VearchClient

    d = 16
    n_docs = 200
    warm, n_meas = (25, 15) if dry else (30, 40)
    rng = np.random.default_rng(7)

    def _pctls(xs):
        ys = sorted(xs)

        def at(q):
            i = min(len(ys) - 1, max(0, int(np.ceil(q * len(ys))) - 1))
            return round(ys[i] * 1e3, 1)

        return {"p50_ms": at(0.5), "p95_ms": at(0.95), "p99_ms": at(0.99)}

    c = StandaloneCluster(
        data_dir=tempfile.mkdtemp(prefix="vearch_tailbench_"), n_ps=3,
        ps_kwargs={"heartbeat_interval": 0.3},
        router_kwargs={"hedge_quantile": 0.5, "hedge_budget_pct": 100.0,
                       "hedge_min_delay_ms": 2.0})
    c.start()
    off_router = RouterServer(master_addr=c.master_addr,
                              hedge_quantile=0.0)
    off_router.start()
    try:
        cl = VearchClient(c.router_addr)
        cl.create_database("db")
        cl.create_space("db", {
            "name": "s", "partition_num": 1, "replica_num": 3,
            "fields": [{"name": "v", "data_type": "vector",
                        "dimension": d,
                        "index": {"index_type": "FLAT",
                                  "metric_type": "L2", "params": {}}}],
        })
        vecs = rng.standard_normal((n_docs, d)).astype(np.float32)
        cl.upsert("db", "s", [{"_id": f"d{i}", "v": vecs[i]}
                              for i in range(n_docs)])

        def timed(addr):
            # unique query per call: every search really scatters
            # (router+PS result caches never serve it)
            q = rng.standard_normal(d).astype(np.float32)
            t0 = time.time()
            _rpc.call(addr, "POST", "/document/search", {
                "db_name": "db", "space_name": "s",
                "vectors": [{"field": "v", "feature": q.tolist()}],
                "limit": 5,
            })
            return time.time() - t0

        # warm both routers (and the hedging router's quantile sketch
        # past its min-sample floor); baseline = the warm stream
        base = [timed(c.router_addr) for _ in range(warm)]
        for _ in range(5):
            timed(off_router.addr)

        part = cl.get_space("db", "s")["partitions"][0]
        ps = next(p for p in c.ps_nodes if p.node_id == part["leader"])
        p50_base_s = sorted(base)[len(base) // 2]
        delay_ms = max(100, int(10 * p50_base_s * 1e3))
        _rpc.call(ps.addr, "POST", "/ps/engine/config", {
            "partition_id": part["id"],
            "config": {"debug_search_delay_ms": delay_ms},
        })
        try:
            h0 = _rpc.call(c.router_addr, "GET",
                           "/router/stats")["hedges"]
            hedged = [timed(c.router_addr) for _ in range(n_meas)]
            h1 = _rpc.call(c.router_addr, "GET",
                           "/router/stats")["hedges"]
            unhedged = [timed(off_router.addr) for _ in range(n_meas)]
        finally:
            _rpc.call(ps.addr, "POST", "/ps/engine/config", {
                "partition_id": part["id"],
                "config": {"debug_search_delay_ms": 0},
            })
        fired = h1["fired"] - h0["fired"]
        won = h1["won"] - h0["won"]
        hp = _pctls(hedged)
        up = _pctls(unhedged)
        return {
            "straggler_delay_ms": delay_ms,
            "baseline": _pctls(base),
            "hedged": hp,
            "unhedged": up,
            "hedge_fired": fired,
            "hedge_won": won,
            "hedge_hit_rate": round(won / fired, 3) if fired else 0.0,
            "hedge_volume_pct": round(100.0 * fired / n_meas, 1),
            "p99_speedup_vs_unhedged": round(
                up["p99_ms"] / hp["p99_ms"], 2) if hp["p99_ms"] else 0.0,
        }
    finally:
        off_router.stop()
        c.stop()


def tiered_storage_bench(dry: bool) -> dict:
    """Tiered storage engine (docs/TIERING.md): a Zipf bucket mix over
    a working set far larger than `cache_mb`, against the same index
    fully HBM-resident. Reports steady-state hit rate, H2D bytes per
    query cold vs warmed (the PCIe ledger), the pin+prefetch hit share
    the convergence gate demands, and the QPS cost of tiering."""
    import tempfile

    from vearch_tpu.engine.disk_vector import DiskRawVectorStore
    from vearch_tpu.engine.types import IndexParams, MetricType
    from vearch_tpu.index.disk import DiskANNIndex
    from vearch_tpu.ops import perf_model

    d = 32
    n, nlist, groups, warm_iters, meas_iters = (
        (20_000, 64, 8, 6, 4) if dry else (400_000, 512, 32, 12, 8)
    )
    rng = np.random.default_rng(11)
    base = rng.standard_normal((n, d)).astype(np.float32)

    def build(cache_mb):
        ddir = tempfile.mkdtemp(prefix="vearch_tierbench_")
        store = DiskRawVectorStore(d, ddir)
        store.add(base)
        p = IndexParams(
            index_type="DISKANN", metric_type=MetricType.L2,
            params={"ncentroids": nlist, "nprobe": 8,
                    "cache_mb": cache_mb, "ram_mb": 64},
        )
        idx = DiskANNIndex(p, store)
        idx.train(base)
        idx.absorb(store.count)
        return idx

    tiered = build(1)  # slots << nlist: the working set cannot fit
    resident = build(512)  # fully resident baseline
    try:
        # Zipf mix over `groups` fixed query batches: batch g repeats
        # with probability ~ 1/(g+1)^1.1, so probe sets recur the way
        # a hot-keyed workload's do
        batches = [
            base[g * 100:g * 100 + 8] + 0.01 for g in range(groups)
        ]
        w = 1.0 / np.power(np.arange(1, groups + 1), 1.1)
        order = rng.choice(groups, size=warm_iters * groups,
                           p=w / w.sum())

        b_cold0 = perf_model.h2d_bytes_total()
        tiered.search(batches[0], 10, None)
        cold_bytes = perf_model.h2d_bytes_total() - b_cold0

        for g in order:  # warm: let pins form, predictor learn
            tiered.search(batches[int(g)], 10, None)
        tiered._prefetcher.drain()

        meas = rng.choice(groups, size=meas_iters * groups,
                          p=w / w.sum())
        st0 = tiered._cache.stats()
        b0 = perf_model.h2d_bytes_total()
        t0 = time.time()
        for g in meas:
            tiered.search(batches[int(g)], 10, None)
        dt_tiered = time.time() - t0
        tiered._prefetcher.drain()
        st1 = tiered._cache.stats()
        steady_bytes = perf_model.h2d_bytes_total() - b0
        lookups = (st1["hits"] + st1["misses"]
                   - st0["hits"] - st0["misses"])
        hits = st1["hits"] - st0["hits"]
        served = (st1["pin_hits"] + st1["prefetch_hits"]
                  - st0["pin_hits"] - st0["prefetch_hits"])

        for g in meas[: len(meas) // 4]:  # warm the baseline too
            resident.search(batches[int(g)], 10, None)
        t0 = time.time()
        for g in meas:
            resident.search(batches[int(g)], 10, None)
        dt_resident = time.time() - t0

        nq = len(meas) * 8
        return {
            "n": n, "d": d, "zipf_groups": groups,
            "hbm_slots": tiered._cache.slots,
            "slab_bytes": tiered._cache.slab_bytes,
            "cold_h2d_bytes_per_query": round(cold_bytes / 8, 1),
            "steady_h2d_bytes_per_query": round(
                steady_bytes / max(nq, 1), 1),
            "steady_hit_rate": round(hits / max(lookups, 1), 3),
            "pin_prefetch_share": round(served / max(lookups, 1), 3),
            "tiered_qps": round(nq / dt_tiered, 1),
            "resident_qps": round(nq / dt_resident, 1),
            "tiering_qps_cost_pct": round(
                100.0 * (1 - (nq / dt_tiered) / (nq / dt_resident)), 1)
            if dt_resident else 0.0,
        }
    finally:
        tiered.close()
        resident.close()


def continuous_batching_bench(dry: bool) -> dict:
    """Continuous-batching scheduler (docs/PERF.md Tier 7): a mixed-
    (k, rows) open-loop workload through the padded-shape-bucket
    scheduler vs the fixed exact-key micro-batcher it replaced
    (`shape_buckets` off). Reports dispatches per query, padding-waste
    share, QPS both ways — and asserts bucketed co-batching is
    bit-identical to solo runs, because a batching win that changes
    results is not a win. The fixed batcher can NOT make that claim:
    its unpadded group shapes hit different XLA reduction strategies
    than a 1-row solo run (gemv vs gemm), so its scores drift in the
    low f32 bits — declared row buckets are what pin every request,
    solo or grouped, to the same program family. Across configs only
    the returned top-k keys are compared, for the same reason."""
    import threading

    from vearch_tpu.engine.engine import Engine, SearchRequest
    from vearch_tpu.engine.types import (
        DataType, FieldSchema, IndexParams, MetricType, TableSchema,
    )

    d = 32
    n_docs, n_reqs, n_workers = (2_000, 240, 16) if dry \
        else (200_000, 4_000, 32)
    rng = np.random.default_rng(11)
    base = rng.standard_normal((n_docs, d)).astype(np.float32)

    # the request mix: mostly single-row lookups at small k, some
    # 2-4 row callers, a few deep-k — the traffic shape that fragmented
    # the old exact-key batcher into solo dispatches
    reqs = []
    for i in range(n_reqs):
        rows = (1, 1, 1, 2, 4)[i % 5]
        k = (3, 5, 10, 10, 20)[i % 5]
        reqs.append((rng.standard_normal((rows, d)).astype(np.float32), k))

    def run(shape_buckets: bool):
        schema = TableSchema("cb", [
            FieldSchema("v", DataType.VECTOR, dimension=d,
                        index=IndexParams("FLAT", MetricType.L2, {})),
        ])
        eng = Engine(schema)
        try:
            eng.upsert([{"_id": str(i), "v": base[i]}
                        for i in range(n_docs)])
            eng.build_index()
            eng.apply_config({"shape_buckets": shape_buckets})
            # warm: one solo query per k so neither run pays first-
            # compile inside the measured window
            for _, k in set((0, k) for _, k in reqs):
                eng.search(SearchRequest(vectors={"v": base[0]}, k=k,
                                         include_fields=[]))
            out = [None] * n_reqs
            errs = []
            it = iter(range(n_reqs))
            lock = threading.Lock()

            def worker():
                while True:
                    with lock:
                        i = next(it, None)
                    if i is None:
                        return
                    q, k = reqs[i]
                    try:
                        out[i] = eng.search(SearchRequest(
                            vectors={"v": q}, k=k, include_fields=[]))
                    except Exception as e:  # pragma: no cover
                        errs.append(e)
                        return

            mb0 = eng._microbatcher
            d0 = mb0.dispatches if mb0 else 0
            threads = [threading.Thread(target=worker, daemon=True,
                                        name=f"bench-cb-{t}")
                       for t in range(n_workers)]
            t0 = time.time()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.time() - t0
            if errs:
                raise errs[0]
            mb = eng._microbatcher
            st = mb.stats() if mb else {}

            def flat(res):
                return [(it_.key, float(it_.score))
                        for it_ in res[0].items] if res else None

            # solo reference on the SAME config: identical padded
            # shapes -> identical program -> the scheduler's results
            # must match bit for bit
            solo = [eng._search_direct(SearchRequest(
                vectors={"v": q}, k=k, include_fields=[]))
                for q, k in reqs]
            return {
                "qps": round(n_reqs / dt, 1),
                "dispatches": int(st.get("dispatches", 0)) - d0,
                "batched_requests": int(st.get("batched_requests", 0)),
                "occupancy_pct": st.get("occupancy_pct", 0.0),
                "pad_real_rows": int(eng.pad_real_rows),
                "pad_padded_rows": int(eng.pad_padded_rows),
                "results": [flat(r) for r in out],
                "solo_results": [flat(r) for r in solo],
            }
        finally:
            eng.close()

    tiered = run(True)
    fixed = run(False)
    identical = tiered["results"] == tiered.pop("solo_results")
    fixed_identical = fixed["results"] == fixed.pop("solo_results")
    same_topk = (
        [[key for key, _ in r] for r in tiered.pop("results")]
        == [[key for key, _ in r] for r in fixed.pop("results")]
    )
    padded = max(tiered["pad_padded_rows"], 1)
    waste_pct = round(
        100.0 * (tiered["pad_padded_rows"] - tiered["pad_real_rows"])
        / padded, 1)
    return {
        "n_docs": n_docs, "n_reqs": n_reqs, "workers": n_workers,
        "bucketed_bit_identical_vs_solo": identical,
        "fixed_bit_identical_vs_solo": fixed_identical,
        "same_topk_vs_fixed": same_topk,
        "bucketed_dispatches_per_query": round(
            tiered["dispatches"] / n_reqs, 3),
        "fixed_dispatches_per_query": round(
            fixed["dispatches"] / n_reqs, 3),
        "dispatch_reduction_x": round(
            fixed["dispatches"] / max(tiered["dispatches"], 1), 2),
        "padding_waste_pct": waste_pct,
        "bucket_occupancy_pct": tiered["occupancy_pct"],
        "bucketed_qps": tiered["qps"],
        "fixed_qps": fixed["qps"],
        "bucketed_batched_requests": tiered["batched_requests"],
        "fixed_batched_requests": fixed["batched_requests"],
    }


def search_quality_bench(dry: bool) -> dict:
    """Search-quality truth layer (docs/QUALITY.md): grounded
    recall@10/@100 for each approximate index family against an exact
    scan of the same corpus, plus the serving cost of the shadow
    sampler — the same query stream with sampling off vs wide open
    (rate 1.0: every query queued, exact-reranked and scored through
    QualityMonitor, drained inline so the worst-case cost is charged
    to the stream). The monitor's own streaming estimate is reported
    next to the offline number it is supposed to track."""
    from vearch_tpu.engine.engine import Engine, SearchRequest
    from vearch_tpu.engine.types import (
        DataType, FieldSchema, IndexParams, MetricType, TableSchema,
    )
    from vearch_tpu.obs.quality import QualityMonitor

    d = 32
    n, nq, nc = (4_000, 16, 32) if dry else (100_000, 64, 512)
    rng = np.random.default_rng(13)
    base = rng.standard_normal((n, d)).astype(np.float32)
    queries = (base[rng.choice(n, nq, replace=False)]
               + 0.05 * rng.standard_normal((nq, d)).astype(np.float32))
    # exact L2 ground truth to depth 100, f64 so ties don't flap
    d2 = ((base.astype(np.float64) ** 2).sum(1)[None, :]
          - 2.0 * queries.astype(np.float64) @ base.astype(np.float64).T)
    gt = np.argsort(d2, axis=1, kind="stable")[:, :100]

    families = {
        "FLAT": ("FLAT", {}),
        "IVFPQ_int8": ("IVFPQ", {"ncentroids": nc, "nsubvector": 8,
                                 "nprobe": max(nc // 8, 8)}),
        "SCANN": ("SCANN", {"ncentroids": nc, "nsubvector": 8,
                            "nprobe": max(nc // 8, 8)}),
        "DISKANN": ("DISKANN", {"ncentroids": nc,
                                "nprobe": max(nc // 8, 8),
                                "cache_mb": 64, "ram_mb": 64}),
    }
    rerank = {"IVFPQ_int8": {"rerank": 128}, "SCANN": {"rerank": 128}}

    def build(itype, params):
        schema = TableSchema("q", [
            FieldSchema("v", DataType.VECTOR, dimension=d,
                        index=IndexParams(itype, MetricType.L2,
                                          {**params,
                                           "training_threshold": n})),
        ])
        eng = Engine(schema)
        for i in range(0, n, 20_000):
            eng.upsert([{"_id": str(j), "v": base[j]}
                        for j in range(i, min(i + 20_000, n))])
        eng.build_index()
        return eng

    def recall_at(eng, k, sp):
        res = eng.search(SearchRequest(vectors={"v": queries}, k=k,
                                       include_fields=[],
                                       index_params=sp))
        got = [[int(it.key) for it in r.items] for r in res]
        return float(np.mean([
            len(set(got[q]) & set(gt[q, :k].tolist())) / k
            for q in range(nq)
        ]))

    out = {"n": n, "d": d, "recall": {}}
    serving = None
    for name, (itype, params) in families.items():
        try:
            eng = build(itype, params)
        except Exception as e:  # one family must not sink the phase
            out["recall"][name] = {"error": f"{type(e).__name__}: {e}"}
            continue
        sp = rerank.get(name, {})
        out["recall"][name] = {
            "at_10": round(recall_at(eng, 10, sp), 4),
            "at_100": round(recall_at(eng, 100, sp), 4),
        }
        if name == "IVFPQ_int8":
            serving = eng  # shadow-overhead subject below
        else:
            eng.close()
    if serving is None:
        return out

    # shadow overhead: the same stream, sampler off vs rate 1.0 with an
    # inline drain after every search (production runs the drain on the
    # worker thread; inline is the upper bound)
    mon = QualityMonitor(get_engines=lambda: {1: serving},
                         pid_space=lambda pid: "bench/q",
                         sample_rate=1.0, min_samples=1)
    sp = rerank["IVFPQ_int8"]
    reps = 3 if dry else 10

    def stream(shadow: bool) -> float:
        t0 = time.time()
        for _ in range(reps):
            for i in range(nq):
                q = queries[i:i + 1]
                res = serving.search(SearchRequest(
                    vectors={"v": q}, k=10, include_fields=[],
                    index_params=sp))
                if shadow:
                    mon.observe_search(
                        1, "bench/q", {"v": q}, 10, res,
                        int(serving.data_version), index_params=sp)
                    mon.run_pending()
        return reps * nq / (time.time() - t0)

    stream(True)  # warm both program families (serve + exact shadow)
    qps_off = stream(False)
    qps_on = stream(True)
    snap = mon.recall_snapshot()["spaces"].get("bench/q", {})
    est = (snap.get("recall") or {}).get("10") or {}
    out["shadow"] = {
        "sample_rate": 1.0,
        "qps_shadow_off": round(qps_off, 1),
        "qps_shadow_on": round(qps_on, 1),
        "overhead_pct": round(100.0 * (1.0 - qps_on / qps_off), 1)
        if qps_off else 0.0,
        "executed": mon.counters().get("executed", 0),
        "estimator_recall_at_10": round(est["estimate"], 4)
        if est.get("estimate") is not None else None,
        "offline_recall_at_10": out["recall"]["IVFPQ_int8"]["at_10"],
    }
    serving.close()
    return out


def progressive_refinement_bench(dry: bool) -> dict:
    """Progressive three-stage refinement (binary -> int8 -> exact) vs
    the int8-only chain (full int8 scan -> exact rerank) at MATCHED
    recall: both chains finish with an exact rerank of their top-r1
    estimate, so with the same r1 the only difference is how the r1
    candidate set is produced — a 1-bit packed stage-0 scan feeding an
    int8 rescore of top-r0, or the full-width int8 scan. Reports
    QPS + recall@10/@100 per chain and the HBM bytes/vector of each
    tier straight from the device ledger (mirror/bit-plane
    device_bytes over capacity, cross-checked against the perf model).
    """
    from vearch_tpu.engine.engine import Engine, SearchRequest
    from vearch_tpu.engine.types import (
        DataType, FieldSchema, IndexParams, MetricType, TableSchema,
    )
    from vearch_tpu.ops import perf_model as pm

    d = 64
    n, nq, nc = (4_000, 16, 32) if dry else (200_000, 64, 512)
    rng = np.random.default_rng(17)
    base = rng.standard_normal((n, d)).astype(np.float32)
    queries = (base[rng.choice(n, nq, replace=False)]
               + 0.05 * rng.standard_normal((nq, d)).astype(np.float32))
    d2 = ((base.astype(np.float64) ** 2).sum(1)[None, :]
          - 2.0 * queries.astype(np.float64) @ base.astype(np.float64).T)
    gt = np.argsort(d2, axis=1, kind="stable")[:, :100]

    schema = TableSchema("pr", [
        FieldSchema("v", DataType.VECTOR, dimension=d,
                    index=IndexParams("IVFRABITQ", MetricType.L2,
                                      {"ncentroids": nc,
                                       "training_threshold": n})),
    ])
    eng = Engine(schema)
    for i in range(0, n, 20_000):
        eng.upsert([{"_id": str(j), "v": base[j]}
                    for j in range(i, min(i + 20_000, n))])
    eng.build_index()
    idx = eng.indexes["v"]

    r1 = min(max(10 * 10, 128), n)          # shared exact-rerank depth
    r0 = min(max(8 * r1, 512), n)           # stage-0 survivor budget
    chains = {
        "three_stage": {"r0": r0, "r1": r1},
        "int8_exact": {"stage0": "off", "rerank": r1},
    }

    def run(sp, k):
        res = eng.search(SearchRequest(vectors={"v": queries}, k=k,
                                       include_fields=[],
                                       index_params=sp))
        return [[int(it.key) for it in r.items] for r in res]

    reps = 3 if dry else 20
    out = {"n": n, "d": d, "r0": r0, "r1": r1, "chains": {}}
    for name, sp in chains.items():
        got10, got100 = run(sp, 10), run(sp, 100)
        t0 = time.time()
        for _ in range(reps):
            run(sp, 10)
        qps = reps * nq / (time.time() - t0)
        out["chains"][name] = {
            "qps": round(qps, 1),
            "recall_at_10": round(float(np.mean([
                len(set(g) & set(gt[q, :10].tolist())) / 10
                for q, g in enumerate(got10)])), 4),
            "recall_at_100": round(float(np.mean([
                len(set(g) & set(gt[q, :100].tolist())) / 100
                for q, g in enumerate(got100)])), 4),
        }
    # HBM bytes per vector, device ledger vs perf model: the stage-0
    # tier must cost <= 1/8 of the int8 mirror's row payload
    cap = idx._bits._h8.shape[0]
    bits_b, mirror_b = idx._bits.device_bytes(), idx._mirror.device_bytes()
    assert bits_b == pm.binary_footprint_bytes(cap, d)
    assert mirror_b == pm.mirror_footprint_bytes(cap, d)
    out["hbm"] = {
        "rows_capacity": cap,
        "bits_bytes_per_vector": round(bits_b / cap, 2),
        "int8_bytes_per_vector": round(mirror_b / cap, 2),
        "plane_payload_ratio": round(
            pm.binary_plane_bytes(cap, d) / (cap * d), 4),
    }
    r10 = {c: out["chains"][c]["recall_at_10"] for c in chains}
    out["recall_gap_at_10"] = round(
        r10["int8_exact"] - r10["three_stage"], 4)
    eng.close()
    return out


def main():
    if _dryrun():
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")
    else:
        _require_device()

    import jax
    import jax.numpy as jnp

    from vearch_tpu.engine.engine import Engine, SearchRequest
    from vearch_tpu.engine.types import (
        DataType, FieldSchema, IndexParams, MetricType, TableSchema,
    )
    from vearch_tpu.ops.distance import brute_force_search

    from vearch_tpu.utils import enable_compilation_cache

    n, d, batch = 1_000_000, 128, 1024
    if _dryrun():
        n, d, batch = 30_000, 32, 64
    capacity = _capacity_mode()
    if capacity:
        # capacity regime row (VERDICT next-4): 16M rows/chip — the int8
        # mirror is 2GB. The query batch shrinks so the [B, N] score
        # matrix stays inside HBM (b=64 -> 4GB f32).
        n, batch = (50_000, 16) if _dryrun() else (16_000_000, 64)

    cache_key = (f"{'cap' if capacity else 'std'}"
                 f"{'_dry' if _dryrun() else ''}_n{n}_d{d}")
    emit, partial_path = _phase_emitter(cache_key)
    # compiled XLA programs also persist across invocations (where
    # JAX_COMPILATION_CACHE_DIR says, else the checkout's fixed path)
    enable_compilation_cache()
    engine_dir = os.path.join(_cache_dir(), f"engine_{cache_key}")
    queries_npz = os.path.join(_cache_dir(), f"queries_{cache_key}.npz")
    emit("start", cache_key=cache_key, partials=partial_path)

    params = {
        "ncentroids": 2048, "nsubvector": 32,
        "train_iters": 8, "training_threshold": 2 * n,
        "store_dtype": "bfloat16",
    }
    if _dryrun():
        params.update(ncentroids=128, nsubvector=16, train_iters=4)

    resumed = (os.path.exists(os.path.join(engine_dir, "engine.json"))
               and os.path.exists(queries_npz))
    t_ingest = t_build = 0.0
    if resumed:
        # retry path: reload the trained engine (training is skipped —
        # raw vectors re-absorb through the persisted
        # centroids/codebooks) instead of paying the build again
        t0 = time.time()
        eng = Engine.open(engine_dir)
        eng.build_index()  # absorb-only: indexes are already trained
        queries = np.load(queries_npz)["queries"]
        emit("load_cached_index", dir=engine_dir,
             load_s=round(time.time() - t0, 1), n=eng.doc_count)
    else:
        base, queries = build_data(n, d)
        schema = TableSchema("bench", [
            FieldSchema("emb", DataType.VECTOR, dimension=d,
                        index=IndexParams("IVFPQ", MetricType.L2, params)),
        ])
        eng = Engine(schema)
        t0 = time.time()
        step = 100_000
        for i in range(0, n, step):
            hi = min(i + step, n)
            eng.upsert([{"_id": f"d{j}", "emb": base[j]}
                        for j in range(i, hi)])
            print(f"ingest {hi}/{n} {time.time()-t0:.0f}s",
                  file=sys.stderr, flush=True)
        t_ingest = time.time() - t0
        emit("ingest", seconds=round(t_ingest, 1), n=n, d=d)
        t0 = time.time()
        eng.build_index()
        t_build = time.time() - t0
        emit("build", seconds=round(t_build, 1))
        try:
            np.savez_compressed(queries_npz, queries=queries)
            eng.dump(engine_dir)
            emit("persist_index", dir=engine_dir)
        except Exception as e:  # caching is best-effort
            emit("persist_index_failed", error=f"{type(e).__name__}: {e}")

    idx = eng.indexes["emb"]
    # raw_results: the columnar serving shape (what the PS wire path
    # consumes) — building b*k python result objects was ~50ms of
    # host time at b=1024 that a TPU-speed kernel cannot hide
    req = SearchRequest(vectors={"emb": queries[:batch]}, k=10,
                        include_fields=[], raw_results=True,
                        index_params={"rerank": 128})
    eng.search(req)  # compile
    t0 = time.time()
    iters = 5
    for _ in range(iters):
        res = eng.search(req)
    dt = (time.time() - t0) / iters
    qps = batch / dt

    # -- roofline denominator: theoretical int8-MXU QPS for this scan
    # shape on the chip jax reports. The dry run has no chip, so it
    # prints no roofline (an assumed chip is not a denominator).
    from vearch_tpu.ops import perf_model

    chip, peak = (None, 0.0) if _dryrun() else perf_model.peak_int8_ops(
        jax.devices()[0].device_kind)
    rdepth_cfg = 128
    roof = (perf_model.roofline_qps(n, d, peak, rerank_r=rdepth_cfg)
            if peak else 0.0)
    roofline_diag = {
        "chip": chip,
        "peak_int8_ops": peak,
        "roofline_qps": round(roof, 1),
        "achieved_qps": round(qps, 1),
        "frac_of_roofline": round(qps / roof, 4) if roof else 0.0,
    }
    emit("qps", batch=batch, qps=round(qps, 1), **roofline_diag)

    # single-query and small-batch latency (engine e2e, min of runs)
    lat = {}
    for b in (1, 32):
        req_b = SearchRequest(vectors={"emb": queries[:b]}, k=10,
                              include_fields=[], raw_results=True,
                              index_params={"rerank": 128})
        eng.search(req_b)  # compile this batch shape
        times = []
        for _ in range(5):
            t0 = time.time()
            eng.search(req_b)
            times.append(time.time() - t0)
        lat[b] = min(times)
    emit("latency", ms_b1=round(lat[1] * 1e3, 1),
         ms_b32=round(lat[32] * 1e3, 1))

    # -- cache effectiveness (multi-tier cache tentpole): replay a
    # Zipfian request mix through the same VersionedLRUCache the
    # router/PS tiers use — the measured effective QPS under a
    # realistic hit rate is the serving win the cache claims, and the
    # Amdahl model (perf_model.effective_qps) is checked against it.
    # Emitted as a partial like every phase (VEARCH_BENCH_CACHE dir).
    from vearch_tpu.cluster.querycache import (
        VersionedLRUCache,
        canonical_query_key,
    )

    pool_n, n_reqs = (20, 200) if _dryrun() else (100, 1000)
    zrng = np.random.default_rng(11)
    # zipf(1.2) ranks capped to the pool: a heavy-tailed popularity
    # curve (few hot queries, long cold tail) instead of uniform reuse
    ranks = np.minimum(zrng.zipf(1.2, size=n_reqs) - 1, pool_n - 1)
    qcache = VersionedLRUCache(max_entries=pool_n)
    misses = 0
    t0 = time.time()
    for i in ranks:
        ckey = canonical_query_key(
            "bench/s", {"emb": queries[i:i + 1]}, 10, None)
        if qcache.get(ckey) is None:
            misses += 1
            r1 = eng.search(SearchRequest(
                vectors={"emb": queries[i:i + 1]}, k=10,
                include_fields=[], raw_results=True,
                index_params={"rerank": 128}))
            qcache.put(ckey, r1)
    t_mix = time.time() - t0
    hit_rate = 1.0 - misses / n_reqs
    cold_qps_b1 = 1.0 / lat[1] if lat[1] else 0.0
    eff_qps = n_reqs / t_mix if t_mix else 0.0
    cache_diag = {
        "pool": pool_n,
        "requests": n_reqs,
        "hit_rate": round(hit_rate, 3),
        "cold_qps_b1": round(cold_qps_b1, 1),
        "effective_qps": round(eff_qps, 1),
        "speedup_vs_cold": round(eff_qps / cold_qps_b1, 2)
        if cold_qps_b1 else 0.0,
        "model_effective_qps": round(
            perf_model.effective_qps(cold_qps_b1, hit_rate), 1),
    }
    emit("cache_effectiveness", **cache_diag)

    # -- side phases, each through a real toy engine/cluster of its own:
    # tail latency (hedging ON vs OFF under an injected straggler),
    # tiered storage (Zipf mix over a beyond-HBM working set),
    # continuous batching (shape buckets vs the exact-key batcher),
    # search quality (grounded recall per index family + shadow-sampler
    # overhead), progressive refinement (binary->int8->exact vs
    # int8->exact). Resumable: a completed record in the partials file
    # is reused. A phase that raises still gets its error line, the
    # later phases still run, and the run then exits non-zero.
    failed_phases: list[str] = []

    def side_phase(name: str, fn) -> dict:
        diag = _phase_cached(partial_path, name)
        if diag is not None:
            emit(f"{name}_resumed", **diag)
            return diag
        try:
            diag = fn(_dryrun())
        except Exception as e:
            diag = {"error": f"{type(e).__name__}: {e}"}
            failed_phases.append(name)
        emit(name, **diag)
        return diag

    tail_diag = side_phase("tail_latency", tail_latency_bench)
    tier_diag = side_phase("tiered_storage", tiered_storage_bench)
    side_phase("continuous_batching", continuous_batching_bench)
    quality_diag = side_phase("quality", search_quality_bench)
    side_phase("progressive_refinement", progressive_refinement_bench)

    # -- per-phase breakdown (r4 review next-1: the captured headline
    # must be decomposable — where does the wall time go?) ------------
    from vearch_tpu.ops import ivf as ivf_ops
    from vearch_tpu.ops.distance import to_device_mask

    store = eng.vector_stores["emb"]
    approx8, mscale, mvsq = idx._mirror.flush()
    basebuf, base_sqn, _ = store.device_buffer()
    dvalid = to_device_mask(None, idx.indexed_count, approx8.shape[0])
    rdepth = min(idx._rerank_depth(10, {"rerank": 128}),
                 max(idx.indexed_count, 1))
    qhost = np.ascontiguousarray(queries[:batch])

    def _best(fn, reps=3):
        times = []
        for _ in range(reps):
            t = time.time()
            fn()
            times.append(time.time() - t)
        return min(times)

    qdev = jnp.asarray(qhost)
    qdev.block_until_ready()
    t_h2d = _best(lambda: jnp.asarray(
        np.array(qhost)).block_until_ready())
    cand = ivf_ops.int8_scan_candidates(
        qdev, approx8, mscale, mvsq, dvalid, rdepth,
        MetricType.L2, "auto")
    jax.block_until_ready(cand)
    t_scan = _best(lambda: jax.block_until_ready(
        ivf_ops.int8_scan_candidates(
            qdev, approx8, mscale, mvsq, dvalid, rdepth,
            MetricType.L2, "auto")))
    cand_i = cand[1]
    t_rerank = _best(lambda: jax.block_until_ready(
        ivf_ops.exact_rerank(qdev.astype(basebuf.dtype), cand_i,
                             basebuf, base_sqn, 10, MetricType.L2)))
    fused_out = ivf_ops.int8_scan_rerank(
        qdev, approx8, mscale, mvsq, dvalid, basebuf, base_sqn,
        rdepth, 10, MetricType.L2, MetricType.L2, "auto",
        idx.mirror_storage)
    jax.block_until_ready(fused_out)
    t_fused = _best(lambda: jax.block_until_ready(
        ivf_ops.int8_scan_rerank(
            qdev, approx8, mscale, mvsq, dvalid, basebuf, base_sqn,
            rdepth, 10, MetricType.L2, MetricType.L2, "auto",
            idx.mirror_storage)))
    t_d2h = _best(lambda: jax.device_get(fused_out))
    t_python = max(dt - (t_h2d + t_fused + t_d2h), 0.0)
    phase_ms = {
        "h2d_query": round(t_h2d * 1e3, 2),
        "kernel_scan": round(t_scan * 1e3, 2),
        "kernel_rerank": round(t_rerank * 1e3, 2),
        "kernel_fused_scan_rerank": round(t_fused * 1e3, 2),
        "d2h_topk": round(t_d2h * 1e3, 2),
        "python_engine_overhead": round(t_python * 1e3, 2),
        "e2e_engine": round(dt * 1e3, 2),
        "kernel_frac_of_e2e": round(t_fused / dt, 3) if dt else 0.0,
        "dispatches_per_search": 1,
    }
    emit("phase_breakdown", **phase_ms)

    # -- mesh_scaling: the pod-slice data plane (docs/POD_SLICE.md) at
    # 1/2/4/8 devices — the same ONE-program fused scan+rerank placed on
    # a make_mesh(n_dev) subset, QPS and frac_of_roofline per count (the
    # roofline denominator scales with the chip count). Resumable like
    # every phase: device counts already in the partials file are
    # skipped on a retry, so a run that died mid-sweep only re-runs
    # the missing counts.
    from vearch_tpu.engine.types import MetricType as _MT
    from vearch_tpu.parallel import mesh as mesh_lib
    from vearch_tpu.parallel.sharded import sharded_ivf_search

    done_counts = set()
    try:
        with open(partial_path) as pf:
            for ln in pf:
                try:
                    prec = json.loads(ln)
                except ValueError:
                    continue
                if prec.get("phase") == "mesh_scaling":
                    done_counts.add(prec.get("devices"))
    except OSError:
        pass
    mesh_diag = {}
    host_mirror = (np.asarray(approx8), np.asarray(mscale),
                   np.asarray(mvsq), np.asarray(dvalid).reshape(-1))
    host_rerank = (np.asarray(basebuf), np.asarray(base_sqn))
    for n_dev in (1, 2, 4, 8):
        if n_dev > len(jax.devices()):
            break
        if n_dev in done_counts:
            mesh_diag[str(n_dev)] = {"resumed": True}
            continue
        m = mesh_lib.make_mesh(n_dev)
        a8_s, _ = mesh_lib.shard_rows(m, host_mirror[0])
        sc_s, _ = mesh_lib.shard_rows(m, host_mirror[1])
        vsq_s, _ = mesh_lib.shard_rows(m, host_mirror[2])
        v_s, _ = mesh_lib.shard_rows(m, host_mirror[3])
        b_s, _ = mesh_lib.shard_rows(m, host_rerank[0])
        bsqn_s, _ = mesh_lib.shard_rows(m, host_rerank[1])
        q_rep = mesh_lib.replicate(
            m, np.ascontiguousarray(queries[:batch], np.float32))

        def _mesh_once(mm=m, a=a8_s, s=sc_s, v=vsq_s, ok=v_s,
                       b=b_s, bs=bsqn_s, q=q_rep):
            return jax.block_until_ready(sharded_ivf_search(
                mm, None, None, a, s, v, ok, b, bs, q,
                rdepth, 10, _MT.L2, _MT.L2, "auto", idx.mirror_storage))

        _mesh_once()  # compile this mesh shape
        t_mesh = _best(_mesh_once)
        qps_m = batch / t_mesh if t_mesh else 0.0
        roof_m = (perf_model.roofline_qps(
            n, d, peak * n_dev, rerank_r=rdepth_cfg) if peak else 0.0)
        row = {
            "qps": round(qps_m, 1),
            "roofline_qps": round(roof_m, 1),
            "frac_of_roofline": round(qps_m / roof_m, 4) if roof_m else 0.0,
        }
        mesh_diag[str(n_dev)] = row
        emit("mesh_scaling", devices=n_dev, batch=batch, **row)
        del a8_s, sc_s, vsq_s, v_s, b_s, bsqn_s, q_rep

    # recall gate vs exact bf16 scan on device
    buf, sqn, _ = store.device_buffer()
    bs, bi = brute_force_search(
        jnp.asarray(queries[:batch], jnp.bfloat16), buf, None, 10,
        MetricType.L2, sqn,
    )
    bi = np.asarray(bi)
    got = [{int(k[1:]) for k in ks} for ks in res.keys]
    recall = float(np.mean([
        len(got[q] & set(bi[q].tolist())) / 10 for q in range(batch)
    ]))
    emit("recall", recall_at_10=round(recall, 4))

    # -- Glove-like COSINE regime (r4 review missing-6: the bench never
    # folded in an angular regime; real Glove is unreachable at zero
    # egress, tests/datasets.py make_glove_like replicates its hard
    # properties: norm spread correlated with cluster mass, low
    # intrinsic dim) --------------------------------------------------
    glove_diag = {}
    try:
        from tests.datasets import make_glove_like

        gn, gd = (8_000, 32) if _dryrun() else (200_000, 100)
        gbase, gq, ggt = make_glove_like(gn, d=gd, nq=64)
        gparams = {"ncentroids": 32 if _dryrun() else 1024,
                   "nsubvector": 8 if _dryrun() else 25,
                   "training_threshold": 2 * gn}
        gschema = TableSchema("glove", [
            FieldSchema("emb", DataType.VECTOR, dimension=gd,
                        index=IndexParams("IVFPQ", MetricType.COSINE,
                                          gparams)),
        ])
        geng = Engine(gschema)
        for i in range(0, gn, 50_000):
            hi = min(i + 50_000, gn)
            geng.upsert([{"_id": str(j), "emb": gbase[j]}
                         for j in range(i, hi)])
        geng.build_index()
        greq = SearchRequest(vectors={"emb": gq}, k=10,
                             include_fields=[],
                             index_params={"rerank": 256})
        geng.search(greq)  # compile
        t0 = time.time()
        gres = geng.search(greq)
        g_dt = time.time() - t0
        ggot = [[int(it.key) for it in r.items] for r in gres]
        g_recall = float(np.mean([
            len(set(ggot[q]) & set(ggt[q][:10].tolist())) / 10
            for q in range(len(ggot))
        ]))
        glove_diag = {"glove_like_cosine": {
            "n": gn, "d": gd, "qps_b64": round(64 / g_dt, 1),
            "recall_at_10": round(g_recall, 4),
        }}
        geng.close()
    except Exception as e:  # error line now, non-zero exit at the end
        glove_diag = {"glove_like_cosine": {"error": str(e)}}
        failed_phases.append("glove")

    emit("glove", **glove_diag.get("glove_like_cosine", {}))
    cpu_qps, cpu_diag = cpu_ivfpq_qps(idx, queries)
    emit("cpu_baseline", **cpu_diag)
    result = {
        "metric": _metric_name(batch),
        "value": round(qps, 1),
        "unit": "qps",
        "vs_baseline": round(qps / cpu_qps, 2),
    }
    if _dryrun():
        # a toy CPU number must never be mistakable for the round's
        # hardware headline if the env var leaks into the harness
        result["metric"] = "DRYRUN_toy_cpu_" + result["metric"]
        result["dryrun"] = True
    diag = {
        "recall_at_10": round(recall, 4),
        "phase_ms": phase_ms,
        "roofline": roofline_diag,
        "mesh_scaling": mesh_diag,
        "cache": cache_diag,
        "tail_latency": tail_diag,
        "tiered_storage": tier_diag,
        "quality": quality_diag,
        **glove_diag,
        **cpu_diag,
        f"latency_ms_b{batch}": round(dt * 1e3, 1),
        "latency_ms_b1": round(lat[1] * 1e3, 1),
        "latency_ms_b32": round(lat[32] * 1e3, 1),
        "ingest_s": round(t_ingest, 1),
        "build_s": round(t_build, 1),
        "resumed_from_cache": resumed,
        "n": n, "d": d,
    }
    print(json.dumps(diag), file=sys.stderr)
    if recall < 0.95:
        print(json.dumps({**result, "error": f"recall gate failed: {recall}"}))
        sys.exit(1)
    if failed_phases:
        print(json.dumps({**result,
                          "error": f"phases failed: {failed_phases}"}))
        sys.exit(1)
    print(json.dumps(result))


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except BaseException as e:  # never end without one parseable JSON line
        import traceback

        traceback.print_exc()
        _emit_error(f"{type(e).__name__}: {e}")
        sys.exit(1)
