"""Chip smoke: the served IVFPQ path, end to end, on the TPU.

    python chip_smoke.py                 # one chip, 1,000,000 x 128
    python chip_smoke.py --chips 4       # the mesh-spanning phase only

What a user of README.md's quick start does, at the repo's headline
deployment (BASELINE.json row 3, the SIFT1M shape): an in-process
StandaloneCluster, a VearchClient over real HTTP to the router, one
IVFPQ space (d=128, L2, ncentroids=2048, nsubvector=32, a float `price`
field), rows upserted over REST, the index built, a few requests — each
checked against an exact float64 numpy reference over the same rows.

This process is the chip's only owner: it asks `jax.devices()` first,
stops unless the platform is `tpu`, and starts no child that touches
JAX. Every earlier stdout line is one JSON object (smoke observations,
not benchmark results); the LAST line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Any phase that fails raises: the exit code is non-zero and no result
line is printed.

`--rehearse-cpu` runs every phase on the CPU backend at whatever
`--rows` / `--ncentroids` say (Pallas in interpret mode) to find wrong
paths before chip time is spent; its last line says `"ok": false` and
it exits with REHEARSAL_RC, never 0.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
#: cluster state (WAL, raft, metastore) of the run; removed at the end
DATA_DIR = os.path.join(REPO, ".chip_smoke_data")
#: exit code of a rehearsal whose phases all passed (not a chip run)
REHEARSAL_RC = 4

DB, SPACE = "smoke", "items"
D, M, K, BATCH = 128, 32, 10, 64
#: exact-rerank depth of the gated requests. Until PR 26 the candidate
#: selection (ops/ivf.py _select_topk) kept 2*max(32, r/4)+8 blocks of
#: 512 rows, an effective depth of ~72 at `rerank: 128`: recall@10 0.92
#: on the chip at 1M rows (PR 22). It now returns the exact top-r by
#: int8 score (0.98 at depth 128 on the CPU; not measured on the chip).
#: The depth-128 reading is printed beside the gated one, itself gated
#: at the upstream 0.80.
RERANK, RERANK_SHALLOW = 256, 128
PRICE_MOD, PRICE_BELOW = 50, 30
INGEST_BATCH = 5000
PRICE_FILTER = {"operator": "AND", "conditions": [
    {"field": "price", "operator": "<", "value": PRICE_BELOW}]}


def emit(phase: str, **kv) -> None:
    print(json.dumps({"phase": phase, **kv}), flush=True)


def make_data(n: int, seed: int):
    """Clustered rows + queries near stored rows (bench.py build_data)."""
    rng = np.random.default_rng(seed)
    nc = max(min(5000, n // 200), 8)
    centers = (rng.standard_normal((nc, D)) * 3).astype(np.float32)
    which = rng.integers(0, nc, n)
    base = centers[which] + 0.7 * rng.standard_normal((n, D)).astype(np.float32)
    q_idx = rng.choice(n, BATCH, replace=False)
    queries = base[q_idx] + 0.1 * rng.standard_normal(
        (BATCH, D)).astype(np.float32)
    return base, queries


class ExactReference:
    """Plain reference: exact float64 L2 top-k over the same rows."""

    def __init__(self, base: np.ndarray):
        self.base = base.astype(np.float64)
        self.sq = (self.base ** 2).sum(1)

    def topk(self, queries: np.ndarray, k: int,
             allowed: np.ndarray | None = None) -> np.ndarray:
        d2 = self.sq[None, :] - 2.0 * (queries.astype(np.float64) @ self.base.T)
        if allowed is not None:
            d2[:, ~allowed] = np.inf
        part = np.argpartition(d2, k, axis=1)[:, :k]
        order = np.argsort(np.take_along_axis(d2, part, 1), axis=1,
                           kind="stable")
        return np.take_along_axis(part, order, 1)


def doc_ids(hits: list[list[dict]]) -> list[list[int]]:
    return [[int(h["_id"][3:]) for h in row if h["_id"].startswith("doc")]
            for row in hits]


def recall_at_k(got: list[list[int]], want: np.ndarray) -> float:
    return float(np.mean([
        len(set(g) & set(w.tolist())) / want.shape[1]
        for g, w in zip(got, want)]))


def id_agreement(a: list[list[int]], b: list[list[int]]) -> float:
    """Share of (query, rank) positions at which two answers name the
    same document."""
    return float(np.mean([x == y for ra, rb in zip(a, b)
                          for x, y in zip(ra, rb)]))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


class Smoke:
    def __init__(self, args, rehearsal: bool):
        self.args, self.rehearsal = args, rehearsal
        self.cluster = None
        self.client = None
        self.ps_addr = ""
        self.pid = ""

    # -- set-up ---------------------------------------------------------------

    def start(self) -> None:
        from vearch_tpu.cluster.standalone import StandaloneCluster
        from vearch_tpu.sdk.client import VearchClient

        shutil.rmtree(DATA_DIR, ignore_errors=True)
        self.cluster = StandaloneCluster(data_dir=DATA_DIR, n_ps=1).start()
        self.client = VearchClient(self.cluster.router_addr)
        self.ps_addr = self.cluster.ps_nodes[0].addr
        self.client.create_database(DB)
        self.client.create_space(DB, {
            "name": SPACE, "partition_num": 1, "replica_num": 1,
            "fields": [
                {"name": "price", "data_type": "float"},
                {"name": "emb", "data_type": "vector", "dimension": D,
                 "index": {"index_type": "IVFPQ", "metric_type": "L2",
                           "params": {
                               "ncentroids": self.args.ncentroids,
                               "nsubvector": M,
                               # the index trains itself when the row
                               # count reaches the threshold: here, on
                               # the last ingest batch
                               "training_threshold": self.args.rows}}},
            ],
        })
        space = self.client.get_space(DB, SPACE)
        self.pid = str(space["partitions"][0]["id"])

    def stop(self) -> None:
        if self.cluster is not None:
            self.cluster.stop()
            # the servers' stop() sets flags; their daemon loops (flush,
            # heartbeat, sampler, shadow worker) notice within seconds.
            # Wait for them: an interpreter that exits while another
            # thread is inside JAX aborts (rc 134). Idle HTTP keep-alive
            # handlers block in recv and never touch the device.
            deadline = time.monotonic() + 15.0
            for t in threading.enumerate():
                if (t is not threading.current_thread()
                        and "process_request_thread" not in t.name):
                    t.join(max(0.0, deadline - time.monotonic()))
        shutil.rmtree(DATA_DIR, ignore_errors=True)

    def ingest(self, base: np.ndarray) -> float:
        n = base.shape[0]
        t0 = time.perf_counter()
        for lo in range(0, n, INGEST_BATCH):
            hi = min(lo + INGEST_BATCH, n)
            out = self.client.upsert(DB, SPACE, [
                {"_id": f"doc{i}", "price": float(i % PRICE_MOD),
                 "emb": base[i]} for i in range(lo, hi)])
            check(out["total"] == hi - lo, f"upsert acked {out}")
            if (lo // INGEST_BATCH) % 20 == 0:
                print(f"ingest {hi}/{n} {time.perf_counter() - t0:.0f}s",
                      file=sys.stderr, flush=True)
        return time.perf_counter() - t0

    def wait_indexed(self, rows: int, timeout_s: float) -> dict:
        """Block until /ps/stats says INDEXED and /ps/jobs says the build
        job is done with no error — so the engine's brute-force fall-back
        after a failed build cannot pass for IVFPQ."""
        from vearch_tpu.cluster import rpc
        from vearch_tpu.engine.types import IndexStatus

        deadline = time.monotonic() + timeout_s
        while True:
            jobs = [j for j in rpc.call(self.ps_addr, "GET", "/ps/jobs")["jobs"]
                    if str(j.get("partition_id")) == self.pid
                    and j.get("op") == "build"]
            part = rpc.call(self.ps_addr, "GET", "/ps/stats")[
                "partitions"][self.pid]
            job = jobs[-1] if jobs else None
            if job is not None and job["status"] == "error":
                raise AssertionError(f"index build failed: {job['error']}")
            if (job is not None and job["status"] == "done"
                    and part["status"] == int(IndexStatus.INDEXED)):
                break
            check(time.monotonic() < deadline,
                  f"index not built after {timeout_s:.0f}s: job={job} "
                  f"partition status={part['status']}")
            time.sleep(1.0)
        check(job["error"] is None, f"build error: {job['error']}")
        check(part["doc_count"] == rows,
              f"partition holds {part['doc_count']} docs, sent {rows}")
        check(job["docs_done"] == rows,
              f"build absorbed {job['docs_done']} of {rows}")
        return job

    def drain_shadow_sampler(self, timeout_s: float = 300.0) -> dict:
        """Wait until the PS's shadow-recall worker has finished every
        sampled request: its exact scans run on the device in a
        background thread, and the process must not exit under one."""
        from vearch_tpu.cluster import rpc

        deadline = time.monotonic() + timeout_s
        while True:
            q = rpc.call(self.ps_addr, "GET", "/ps/stats")["quality"]
            c = q["sampling"]["counters"]
            finished = sum(c.get(k, 0) for k in (
                "executed", "dropped", "stale", "shed", "error"))
            if q["sampling"]["queue"] == 0 and finished >= c.get("sampled", 0):
                return {"rate": q["sampling"]["rate"], **c}
            check(time.monotonic() < deadline,
                  f"shadow sampler still busy after {timeout_s:.0f}s: {c}")
            time.sleep(0.2)

    # -- requests -------------------------------------------------------------

    def search(self, queries: np.ndarray, params: dict | None = None,
               filters: dict | None = None):
        """One profiled, cache-bypassing request through the router;
        returns (per-query hits, partition profile, seconds)."""
        t0 = time.perf_counter()
        out = self.client.search(
            DB, SPACE, vectors=[{"field": "emb", "feature": queries}],
            limit=K, filters=filters, fields=[],
            index_params={"rerank": RERANK, **(params or {})},
            profile=True, cache=False)
        dt = time.perf_counter() - t0
        prof = out["profile"]
        check(prof["cache"] == "bypass", f"router cache: {prof['cache']}")
        return out["documents"], prof["partitions"][self.pid], dt

    def timed(self, kind: str, tag: str, queries, params=None, filters=None,
              repeats: int = 5):
        """First call (compiles), then `repeats` warmed calls that must
        add no compiled program; asserts the profile names `tag`."""
        from vearch_tpu.ops import perf_model

        hits, prof, first_s = self.search(queries, params, filters)
        tags = prof["dispatches"]["tags"]
        check(tag in tags, f"{kind}: profile names {tags}, expected {tag}")
        before = perf_model.compiled_program_counts()
        warm = []
        for _ in range(repeats):
            again, _, dt = self.search(queries, params, filters)
            check(doc_ids(again) == doc_ids(hits),
                  f"{kind}: a repeat returned different ids")
            warm.append(dt * 1e3)
        after = perf_model.compiled_program_counts()
        grew = {k: (before.get(k, 0), v) for k, v in after.items()
                if v != before.get(k, 0)}
        # the 1% shadow-recall sampler (obs/quality.py) may run its exact
        # scan beside a repeat; any other growth is a serving-path retrace
        check(all(k.startswith("distance.") for k in grew),
              f"{kind}: warmed repeats compiled new programs: {grew}")
        return hits, prof, {
            "kind": kind, "rows_in_request": int(np.atleast_2d(queries).shape[0]),
            "rerank": (params or {}).get("rerank", RERANK),
            "dispatches": tags, "perf_path": prof["dispatches"]["path"],
            "kernels": prof["dispatches"]["kernels"],
            "first_call_s": first_s,
            "warmed_median_ms": float(np.median(warm)),
            "warmed_ms": warm,
            "shadow_sampler_compiles": sorted(grew),
        }

    # -- phases ---------------------------------------------------------------

    def one_chip(self, base, queries, ref: ExactReference) -> None:
        from vearch_tpu.ops import perf_model

        want = ref.topk(queries, K)

        hits, _, obs = self.timed("single", "fused_scan_rerank", queries[0])
        got = doc_ids(hits)
        check(len(got) == 1 and len(got[0]) == K, f"single: {hits}")
        check(got[0][0] == int(want[0, 0]),
              f"single: top-1 {got[0][0]} != exact {int(want[0, 0])}")
        emit("request", **obs, recall_at_10=recall_at_k(got, want[:1]))

        hits, _, obs = self.timed("batch64", "fused_scan_rerank", queries)
        rec = recall_at_k(doc_ids(hits), want)
        emit("request", **obs, recall_at_10=rec)
        check(rec >= 0.95, f"batch64 recall@10 {rec} < 0.95")

        hits, _, obs = self.timed("batch64_rerank128", "fused_scan_rerank",
                                  queries, {"rerank": RERANK_SHALLOW})
        rec = recall_at_k(doc_ids(hits), want)
        emit("request", **obs, recall_at_10=rec)
        check(rec >= 0.80, f"batch64 rerank=128 recall@10 {rec} < 0.80")

        allowed = (np.arange(base.shape[0]) % PRICE_MOD) < PRICE_BELOW
        hits, _, obs = self.timed("batch64_filtered", "fused_scan_rerank",
                                  queries, filters=PRICE_FILTER)
        got = doc_ids(hits)
        check(all(allowed[i] for row in got for i in row),
              "filtered: a hit violates price < 30")
        rec = recall_at_k(got, ref.topk(queries, K, allowed))
        emit("request", **obs, recall_at_10=rec,
             filter_pass_fraction=float(allowed.mean()))
        check(rec >= 0.95, f"filtered recall@10 {rec} < 0.95")

        # past the full-scan cliff the index serves from bucket-grouped
        # lists; force that regime per request. On the chip the default
        # probe kernel must be the Mosaic-compiled Pallas one; the
        # rehearsal asks for it by name (interpret mode on the CPU).
        probe = {"scan_mode": "probe"}
        if self.rehearsal:
            probe["probe_kernel"] = "pallas"
        hits_p, _, obs_p = self.timed("batch64_probe_pallas", "probe_scan",
                                      queries, probe)
        check(obs_p["kernels"] == {"probe_scan": "pallas"},
              f"probe phase served by {obs_p['kernels']}, not the Pallas "
              f"kernel")
        hits_x, _, obs_x = self.timed(
            "batch64_probe_xla", "probe_scan", queries,
            {"scan_mode": "probe", "probe_kernel": "xla"})
        check(obs_x["kernels"] == {"probe_scan": "xla"}, str(obs_x["kernels"]))
        gp, gx = doc_ids(hits_p), doc_ids(hits_x)
        agree = id_agreement(gp, gx)
        rec_p, rec_x = recall_at_k(gp, want), recall_at_k(gx, want)
        emit("request", **obs_p, recall_at_10=rec_p)
        emit("request", **obs_x, recall_at_10=rec_x,
             pallas_xla_id_agreement=agree)
        check(agree >= 0.99, f"pallas/xla probe agree on {agree} of ids")
        check(min(rec_p, rec_x) >= 0.80,
              f"probe recall@10 {rec_p}/{rec_x} < 0.80")

        emit("compiled_programs",
             after_warm_up=perf_model.total_compiled_programs(),
             by_program={k: v for k, v in
                         perf_model.compiled_program_counts().items() if v})
        self.write_then_delete(queries[1])

    def write_then_delete(self, near: np.ndarray) -> None:
        """An acknowledged write is read back by id and found by search;
        after an acknowledged delete it is gone from both."""
        new_id, vec = "smoke_new", (near + 40.0).astype(np.float32)
        out = self.client.upsert(DB, SPACE, [
            {"_id": new_id, "price": 1.0, "emb": vec}])
        check(out["total"] == 1, f"upsert ack: {out}")
        docs = self.client.query(DB, SPACE, document_ids=[new_id],
                                 vector_value=True)
        check(len(docs) == 1 and docs[0]["_id"] == new_id
              and docs[0]["price"] == 1.0
              and np.allclose(docs[0]["emb"], vec), f"read back: {docs}")
        hits, _, _ = self.search(vec)
        check(hits[0][0]["_id"] == new_id,
              f"search after write: top hit {hits[0][:2]}")
        check(self.client.delete(DB, SPACE, document_ids=[new_id]) == 1,
              "delete not acknowledged")
        docs = self.client.query(DB, SPACE, document_ids=[new_id])
        check(docs == [], f"read after delete: {docs}")
        hits, _, _ = self.search(vec)
        check(all(h["_id"] != new_id for h in hits[0]),
              "deleted id still found by search")
        emit("write_read_delete", upsert_read_back=True, found_by_search=True,
             gone_after_delete=True)

    def four_chips(self, queries, ref: ExactReference) -> None:
        """The mesh-spanning partition vs the same partition served from
        one device, both against the exact reference."""
        import jax

        from vearch_tpu.cluster import rpc
        from vearch_tpu.obs.sampler import device_label, measure_live_bytes

        want = ref.topk(queries, K)
        hits_m, prof_m, obs_m = self.timed(
            "batch64_mesh", "sharded_fused_scan_rerank", queries)
        rec_m = recall_at_k(doc_ids(hits_m), want)
        # the program as the device trace will name it (XLA names a
        # module after the jitted function), and how long each of the
        # six dispatches took to launch (kernel.* span tag `launch_us`)
        from vearch_tpu.cluster import tracing
        from vearch_tpu.ops import perf_model

        modules = sorted({
            f"jit_{fn.__name__}" for label, fn in
            perf_model._JIT_REGISTRY.items()
            if label.startswith("sharded.ivf_fused[") and fn._cache_size()})
        launch_us = [s.tags.get("launch_us") for s in tracing.snapshot()
                     if s.name == "kernel.sharded_fused_scan_rerank"]
        emit("request", **obs_m, recall_at_10=rec_m, mesh=prof_m.get("mesh"),
             program=modules, launch_us=launch_us)
        check(modules == ["jit_sharded_fused_scan_rerank"],
              f"the mesh program is on the trace as {modules}")
        check(len(launch_us) == 6 and all(u is not None and u > 0
                                          for u in launch_us),
              f"mesh dispatches without launch_us: {launch_us}")

        # Where the mirror and the rerank store live, BEFORE the
        # single-device comparison places its own full copy on device 0:
        # the bytes of every live array that is split over the mesh, per
        # device, against what the placement caches say they uploaded.
        # Total live bytes per device are reported beside it, not
        # asserted: the 1% shadow-recall sampler's exact scan
        # (obs/quality.py -> FlatIndex) still places an UNSHARDED raw
        # copy on device 0 of a mesh partition (PERF.md, open questions).
        mesh = rpc.call(self.ps_addr, "GET", "/ps/stats")[
            "partitions"][self.pid]["mesh"]["fields"]["emb"]
        placed = (mesh["mirror_placement"]["h2d_bytes"]
                  + mesh["raw_placement"]["h2d_bytes"])
        live = measure_live_bytes()
        sharded = {device_label(d): 0 for d in jax.devices()}
        for arr in jax.live_arrays():
            if arr.is_fully_replicated or len(arr.sharding.device_set) < 2:
                continue
            for sh in arr.addressable_shards:
                sharded[device_label(sh.device)] += int(sh.data.nbytes)
        share = {k: v / max(sum(sharded.values()), 1)
                 for k, v in sharded.items()}
        emit("mesh_residency", devices=mesh["devices"],
             data_shards=mesh["data_shards"], placed_bytes=placed,
             sharded_bytes=sharded, sharded_share=share, live_bytes=live,
             unsharded_bytes={k: live[k] - sharded[k] for k in sharded})
        check(mesh["devices"] == 4 and mesh["data_shards"] == 4, str(mesh))
        check(len(sharded) == 4
              and all(0.20 <= v <= 0.30 for v in share.values()),
              f"mirror + rerank store not spread four ways: {sharded}")
        check(min(sharded.values()) >= 0.95 * placed / 4,
              f"a device holds less than a quarter of the {placed} placed "
              f"bytes: {sharded}")

        hits_s, _, obs_s = self.timed("batch64_single_device",
                                      "fused_scan_rerank", queries,
                                      {"mesh_serving": "off"})
        rec_s = recall_at_k(doc_ids(hits_s), want)
        agree = id_agreement(doc_ids(hits_m), doc_ids(hits_s))
        emit("request", **obs_s, recall_at_10=rec_s,
             mesh_single_id_agreement=agree)
        check(min(rec_m, rec_s) >= 0.95,
              f"recall@10 mesh {rec_m} / single device {rec_s} < 0.95")
        check(agree >= 0.99, f"mesh and single device agree on {agree}")


def cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except FileNotFoundError:
        return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--ncentroids", type=int, default=2048)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from vearch_tpu import native
    from vearch_tpu.utils import enable_compilation_cache

    cache_dir = enable_compilation_cache()  # before the first compile
    devs = jax.devices()  # raises the backend's own error if init fails
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    rehearsal = args.rehearse_cpu and dev["platform"] == "cpu"
    if dev["platform"] != "tpu" and not rehearsal:
        print(f"chip_smoke: no TPU: jax found {dev}. This script runs on "
              f"the chip; --rehearse-cpu rehearses it on the CPU backend.",
              file=sys.stderr)
        return 1
    if dev["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{dev['count']} devices", file=sys.stderr)
        return 1
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None

    hits_misses = {"hits": 0, "misses": 0}

    def on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            hits_misses["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            hits_misses["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    entries0 = cache_entries(cache_dir)
    emit("device", **dev, jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, native_helpers=native.available(),
         rehearsal=rehearsal, compile_cache_dir=cache_dir,
         compile_cache_entries_at_start=entries0)

    t0 = time.perf_counter()
    base, queries = make_data(args.rows, args.seed)
    ref = ExactReference(base)
    emit("data", rows=args.rows, d=D, seed=args.seed, queries=BATCH,
         chips=args.chips, ncentroids=args.ncentroids, nsubvector=M,
         rows_cut_from_default=args.rows < ap.get_default("rows"),
         seconds=time.perf_counter() - t0)

    smoke = Smoke(args, rehearsal)
    try:
        smoke.start()
        ingest_s = smoke.ingest(base)
        emit("ingest", rows=args.rows, seconds=ingest_s,
             docs_per_s=args.rows / ingest_s, transport="REST via router",
             batch_docs=INGEST_BATCH)
        job = smoke.wait_indexed(args.rows, timeout_s=900.0)
        emit("build", status=job["status"], error=job["error"],
             partition_status="INDEXED", docs_done=job["docs_done"],
             seconds=job["duration_seconds"], phases_ms=job["phases_ms"],
             train_mesh=job.get("train_mesh"))
        if args.chips == 4:
            smoke.four_chips(queries, ref)
        else:
            smoke.one_chip(base, queries, ref)
        emit("shadow_sampler", **smoke.drain_shadow_sampler())
        stats = [d.memory_stats() or {} for d in devs]
        emit("memory", bytes_in_use=[s.get("bytes_in_use") for s in stats],
             peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
             bytes_limit=[s.get("bytes_limit") for s in stats])
        emit("compile_cache", dir=cache_dir, entries_at_start=entries0,
             entries_at_end=cache_entries(cache_dir),
             persistent_hits=hits_misses["hits"],
             fresh_compiles=hits_misses["misses"])
    finally:
        smoke.stop()

    print(json.dumps({"ok": not rehearsal, "device": dev}), flush=True)
    return REHEARSAL_RC if rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
