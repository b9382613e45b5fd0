#!/usr/bin/env python
"""Per-index benchmark suite: QPS / latency / recall for every index
type (reference: scripts/benchmarks/{restful,pysdk,utils}.py — per-index
QPS+recall scripts driven against a running engine; here the engine is
in-process, so the suite drives Engine directly and measures the same
three things).

One JSON line per (index, batch) combination:
  {"index": "IVFPQ", "n": ..., "d": ..., "batch": ...,
   "qps": ..., "p50_ms": ..., "recall_at_10": ...,
   "ingest_s": ..., "build_s": ...}

Run: python scripts/benchmarks/per_index.py [--n 200000] [--d 128]
       [--indexes IVFPQ,HNSW,...] [--batches 1,32,1024] [--hard]
CPU-safe at small --n; on TPU use the defaults.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from tests.datasets import make_easy, make_hard  # noqa: E402
from vearch_tpu.engine.engine import Engine, SearchRequest  # noqa: E402
from vearch_tpu.engine.types import (  # noqa: E402
    DataType, FieldSchema, IndexParams, MetricType, TableSchema,
)

# per-index build params, scaled for the default 200k x 128 config
# (ncentroids ~ 4*sqrt(n) like the reference's benchmark scripts)
PARAMS = {
    "FLAT": {},
    "IVFFLAT": {"ncentroids": 1024, "nprobe": 64},
    "IVFPQ": {"ncentroids": 1024, "nsubvector": 32, "nprobe": 64},
    "IVFRABITQ": {"ncentroids": 1024, "nprobe": 64},
    "SCANN": {"ncentroids": 1024, "nsubvector": 32, "nprobe": 64},
    "HNSW": {"nlinks": 32, "efSearch": 64, "efConstruction": 160},
}
SEARCH_PARAMS = {
    "IVFPQ": {"rerank": 128},
    "IVFRABITQ": {"rerank": 256},
    "SCANN": {"rerank": 128},
}


def bench_index(itype: str, base, queries, gt, batches, metric) -> None:
    n, d = base.shape
    params = dict(PARAMS.get(itype, {}))
    params["training_threshold"] = n
    schema = TableSchema("b", [
        FieldSchema("v", DataType.VECTOR, dimension=d,
                    index=IndexParams(itype, metric, params)),
    ])
    eng = Engine(schema)
    t0 = time.time()
    step = 20_000
    for i in range(0, n, step):
        eng.upsert([{"_id": str(j), "v": base[j]}
                    for j in range(i, min(i + step, n))])
    ingest_s = time.time() - t0
    t0 = time.time()
    eng.build_index()
    build_s = time.time() - t0

    sp = SEARCH_PARAMS.get(itype, {})
    # recall on the full query set at k=10
    res = eng.search(SearchRequest(vectors={"v": queries}, k=10,
                                   include_fields=[], index_params=sp))
    got = [[int(it.key) for it in r.items] for r in res]
    recall = float(np.mean([
        len(set(got[q]) & set(gt[q][:10].tolist())) / 10
        for q in range(len(got))
    ]))

    for batch in batches:
        qb = np.tile(queries, (max(1, batch // len(queries) + 1), 1))[:batch]
        req = SearchRequest(vectors={"v": qb}, k=10, include_fields=[],
                            index_params=sp)
        eng.search(req)  # warm (compile)
        lats = []
        t_end = time.time() + 3.0
        while time.time() < t_end:
            t1 = time.time()
            eng.search(req)
            lats.append(time.time() - t1)
        lats.sort()
        p50 = lats[len(lats) // 2]
        print(json.dumps({
            "index": itype, "n": n, "d": d, "batch": batch,
            "qps": round(batch / p50, 1),
            "p50_ms": round(p50 * 1e3, 3),
            "recall_at_10": round(recall, 4),
            "ingest_s": round(ingest_s, 1),
            "build_s": round(build_s, 1),
        }), flush=True)


def bench_binaryivf(n, nq, batches) -> None:
    rng = np.random.default_rng(11)
    dbits = 256
    nc = max(n // 300, 16)
    centers = rng.integers(0, 2, (nc, dbits), dtype=np.uint8)
    which = rng.integers(0, nc, n)
    bits = centers[which] ^ (rng.random((n, dbits)) < 0.10).astype(np.uint8)
    packed = np.packbits(bits, axis=1)
    q_idx = rng.choice(n, nq, replace=False)
    qbits = bits[q_idx] ^ (rng.random((nq, dbits)) < 0.08).astype(np.uint8)
    qpacked = np.packbits(qbits, axis=1)
    ham = (qbits[:, None, :] ^ bits[None, :, :]).sum(axis=2)
    gt = np.argsort(ham, axis=1, kind="stable")[:, :10]

    schema = TableSchema("b", [
        FieldSchema("v", DataType.VECTOR, dimension=dbits,
                    index=IndexParams("BINARYIVF", MetricType.L2, {
                        "ncentroids": max(nc, 64), "nprobe": 16,
                        "training_threshold": n})),
    ])
    eng = Engine(schema)
    t0 = time.time()
    for i in range(0, n, 20_000):
        eng.upsert([{"_id": str(j), "v": packed[j]}
                    for j in range(i, min(i + 20_000, n))])
    ingest_s = time.time() - t0
    t0 = time.time()
    eng.build_index()
    build_s = time.time() - t0
    res = eng.search(SearchRequest(vectors={"v": qpacked}, k=10,
                                   include_fields=[]))
    got = [[int(it.key) for it in r.items] for r in res]
    recall = float(np.mean([
        len(set(got[q]) & set(gt[q].tolist())) / 10 for q in range(nq)
    ]))
    for batch in batches:
        qb = np.tile(qpacked, (max(1, batch // nq + 1), 1))[:batch]
        req = SearchRequest(vectors={"v": qb}, k=10, include_fields=[])
        eng.search(req)
        lats = []
        t_end = time.time() + 3.0
        while time.time() < t_end:
            t1 = time.time()
            eng.search(req)
            lats.append(time.time() - t1)
        lats.sort()
        p50 = lats[len(lats) // 2]
        print(json.dumps({
            "index": "BINARYIVF", "n": n, "d": dbits, "batch": batch,
            "qps": round(batch / p50, 1),
            "p50_ms": round(p50 * 1e3, 3),
            "recall_at_10": round(recall, 4),
            "ingest_s": round(ingest_s, 1),
            "build_s": round(build_s, 1),
        }), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--nq", type=int, default=64)
    ap.add_argument("--indexes", default="FLAT,IVFFLAT,IVFPQ,IVFRABITQ,"
                                         "SCANN,HNSW,BINARYIVF")
    ap.add_argument("--batches", default="1,32,1024")
    ap.add_argument("--hard", action="store_true",
                    help="use the hard dataset regime (power-law + "
                         "anisotropic + OOD; tests/datasets.py)")
    args = ap.parse_args()

    batches = [int(b) for b in args.batches.split(",")]
    gen = make_hard if args.hard else make_easy
    base, queries, gt = gen(args.n, args.d, args.nq)
    for itype in args.indexes.split(","):
        itype = itype.strip().upper()
        if itype == "BINARYIVF":
            bench_binaryivf(min(args.n, 100_000), args.nq, batches)
            continue
        metric = (MetricType.INNER_PRODUCT if itype == "SCANN"
                  else MetricType.L2)
        if itype == "SCANN":
            q64 = queries.astype(np.float64)
            gt_i = np.argsort(-(q64 @ base.astype(np.float64).T),
                              axis=1)[:, :10]
        else:
            gt_i = gt
        bench_index(itype, base, queries, gt_i, batches, metric)
    return 0


if __name__ == "__main__":
    sys.exit(main())
