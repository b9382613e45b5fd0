#!/usr/bin/env python
"""Cluster-path benchmark: QPS / p50 / p99 / recall per index type
through a LIVE standalone cluster's REST route (reference:
scripts/benchmarks/restful.py — the reference benches end-to-end REST;
the r4 review flagged that this repo's transport-layer wins lived on a
path nothing measured).

For each (index, batch):
  1. engine-direct numbers on the SAME data in the same process
     (the per_index.py path), then
  2. the full router path: SDK -> router scatter/gather -> PS -> engine,
and prints both JSON rows plus the router-overhead delta.

One JSON line per row:
  {"path": "engine"|"rest", "index": ..., "batch": ...,
   "qps": ..., "p50_ms": ..., "p99_ms": ..., "recall_at_10": ...}
  {"path": "delta", "index": ..., "batch": ...,
   "router_overhead_ms_p50": ..., "rest_over_engine_qps": ...}

Run: python scripts/benchmarks/restful.py [--n 200000] [--partitions 3]
       [--indexes FLAT,IVFPQ] [--batches 1,32,1024]
CPU-safe at small --n; on TPU use the defaults.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from tests.datasets import make_easy, make_hard  # noqa: E402
from vearch_tpu.cluster.standalone import StandaloneCluster  # noqa: E402
from vearch_tpu.engine.engine import Engine, SearchRequest  # noqa: E402
from vearch_tpu.engine.types import (  # noqa: E402
    DataType, FieldSchema, IndexParams, MetricType, TableSchema,
)
from vearch_tpu.sdk.client import VearchClient  # noqa: E402

PARAMS = {
    "FLAT": {},
    "IVFFLAT": {"ncentroids": 1024, "nprobe": 64},
    "IVFPQ": {"ncentroids": 1024, "nsubvector": 32, "nprobe": 64},
}
SEARCH_PARAMS = {"IVFPQ": {"rerank": 128}}


def _percentiles(lats: list[float]) -> tuple[float, float]:
    lats = sorted(lats)
    p50 = lats[len(lats) // 2]
    p99 = lats[min(int(len(lats) * 0.99), len(lats) - 1)]
    return p50, p99


def _measure(call, batch: int, seconds: float) -> dict:
    call()  # warm/compile
    lats = []
    t_end = time.time() + seconds
    while time.time() < t_end:
        t1 = time.time()
        call()
        lats.append(time.time() - t1)
    p50, p99 = _percentiles(lats)
    return {"qps": round(batch / p50, 1), "p50_ms": round(p50 * 1e3, 3),
            "p99_ms": round(p99 * 1e3, 3)}


def _recall(got: list[list[int]], gt: np.ndarray) -> float:
    return float(np.mean([
        len(set(got[q]) & set(gt[q][:10].tolist())) / 10
        for q in range(len(got))
    ]))


def bench_both(itype: str, base, queries, gt, batches, partitions,
               seconds) -> None:
    n, d = base.shape
    params = dict(PARAMS.get(itype, {}))
    params["training_threshold"] = n
    metric = MetricType.L2
    sp = SEARCH_PARAMS.get(itype, {})

    # -- engine-direct (per_index.py path) on the same data -----------
    schema = TableSchema("b", [
        FieldSchema("v", DataType.VECTOR, dimension=d,
                    index=IndexParams(itype, metric, params)),
    ])
    eng = Engine(schema)
    for i in range(0, n, 20_000):
        eng.upsert([{"_id": str(j), "v": base[j]}
                    for j in range(i, min(i + 20_000, n))])
    eng.build_index()
    res = eng.search(SearchRequest(vectors={"v": queries}, k=10,
                                   include_fields=[], index_params=sp))
    eng_recall = _recall(
        [[int(it.key) for it in r.items] for r in res], gt)

    engine_rows = {}
    for batch in batches:
        qb = np.tile(queries, (max(1, batch // len(queries) + 1), 1))[:batch]
        req = SearchRequest(vectors={"v": qb}, k=10, include_fields=[],
                            index_params=sp)
        row = _measure(lambda: eng.search(req), batch, seconds)
        engine_rows[batch] = row
        print(json.dumps({
            "path": "engine", "index": itype, "n": n, "d": d,
            "batch": batch, **row, "recall_at_10": round(eng_recall, 4),
            "partitions": 1,
        }), flush=True)
    eng.close()

    # -- REST path through a live cluster -----------------------------
    c = StandaloneCluster(data_dir=tempfile.mkdtemp(prefix="bench_rest."),
                         n_ps=min(2, partitions))
    c.start()
    try:
        cl = VearchClient(c.router_addr)
        cl.create_database("bench")
        cl.create_space("bench", {
            "name": itype.lower(), "partition_num": partitions,
            "replica_num": 1,
            "fields": [{"name": "v", "data_type": "vector", "dimension": d,
                        "index": {"index_type": itype,
                                  "metric_type": "L2", "params": params}}],
        })
        for i in range(0, n, 5_000):
            hi = min(i + 5_000, n)
            cl.upsert("bench", itype.lower(), [
                {"_id": str(j), "v": base[j]} for j in range(i, hi)
            ])
        cl.forcemerge("bench", itype.lower())
        # readiness: probe until the first search answers (background
        # builds may still be absorbing across partitions)
        deadline = time.time() + 600
        while time.time() < deadline:
            time.sleep(1.0)
            got = cl.search("bench", itype.lower(),
                            [{"field": "v", "feature": queries[0]}],
                            limit=10, fields=[],
                            index_params=sp)
            if got and got[0]:
                break

        res = cl.search("bench", itype.lower(),
                        [{"field": "v",
                          "feature": np.ascontiguousarray(queries).ravel()}],
                        limit=10, fields=[], index_params=sp)
        rest_recall = _recall(
            [[int(it["_id"]) for it in r] for r in res], gt)

        for batch in batches:
            qb = np.tile(queries,
                         (max(1, batch // len(queries) + 1), 1))[:batch]
            flat = np.ascontiguousarray(qb).ravel()

            def call():
                cl.search("bench", itype.lower(),
                          [{"field": "v", "feature": flat}],
                          limit=10, fields=[], columnar=True,
                          index_params=sp)

            row = _measure(call, batch, seconds)
            print(json.dumps({
                "path": "rest", "index": itype, "n": n, "d": d,
                "batch": batch, **row,
                "recall_at_10": round(rest_recall, 4),
                "partitions": partitions,
            }), flush=True)
            erow = engine_rows[batch]
            print(json.dumps({
                "path": "delta", "index": itype, "batch": batch,
                "router_overhead_ms_p50": round(
                    row["p50_ms"] - erow["p50_ms"], 3),
                "rest_over_engine_qps": round(
                    row["qps"] / max(erow["qps"], 1e-9), 3),
            }), flush=True)
    finally:
        c.stop()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--nq", type=int, default=64)
    ap.add_argument("--partitions", type=int, default=3)
    ap.add_argument("--indexes", default="FLAT,IVFPQ")
    ap.add_argument("--batches", default="1,32,1024")
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="measure window per (index, batch)")
    ap.add_argument("--hard", action="store_true")
    args = ap.parse_args()
    batches = [int(b) for b in args.batches.split(",")]
    gen = make_hard if args.hard else make_easy
    base, queries, gt = gen(args.n, args.d, args.nq)
    for itype in args.indexes.split(","):
        bench_both(itype.strip().upper(), base, queries, gt, batches,
                   args.partitions, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
