"""Needed work of the IVF-Flat probe scan (`ops/ivf.py`
`ivfflat_candidates`, XLA module `jit_ivfflat_candidates`), from shapes
alone.

Needed work is the algorithm's, whatever implements it: each query row
is scored against the `nlist` centroids (2*nlist*d operations) and
against every row of the `nprobe` lists it probes, N / nlist rows a
list in the mean (2*nprobe*(N/nlist)*d), and its r results come back.
`nlist` and `nprobe` are the configuration's
(`configs/sift1m-ivfflat.json`: the reader hands over rows, N, d and r
only).

Which bound was taken, and why. It must hold for ANY implementation of
the same semantics, so that no later PR can read over 100 %:
- operations at the bf16 peak, though the configuration promises
  float32 at `highest` (several passes of the matrix unit, or none of
  it): the published peak has no float32 column, and a lower bound may
  be generous;
- bytes per DISPATCH: the centroids and ONE pass over `nprobe` lists of
  mean length, rows, squared norms and ids. The program as it stands
  gathers `nprobe` lists per QUERY (64 x that), and an implementation
  that reads each probed list once for all the queries of a dispatch
  that probe it still reads the union of their lists, which is no
  smaller than one query's own `nprobe`. Of 64 queries' lists few
  coincide, so the true need lies far above this; below it nothing can;
- bytes per ROW: the query and its r (score, id) results.
Not needed work: the padding every list carries up to `cap`, the
gather's write and re-read of [B, tile, d], the folds' top_k over
[B, r + tile], the mask's per-slot gather. They are what the later
`perf_opt` PRs have to win (`ivf_gather_pct`, `ivf_fold_topk_pct`,
`ivf_bucket_fill_pct`).
"""

from __future__ import annotations

import json
import os

from benchmark.kernels.int8_scan_rerank import least_seconds  # noqa: F401

#: how the program appears among the device trace's XLA modules
MODULE_SUBSTRING = "ivfflat_candidates"

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "sift1m-ivfflat.json")) as _f:
    _CFG = json.load(_f)
NLIST = int(next(f["index"]["params"]["ncentroids"]
                 for f in _CFG["space"]["fields"] if f.get("index")))
NPROBE = int(_CFG["search"]["index_params"]["nprobe"])


def needed(rows: int, n: int, d: int, r: int, raw_bytes: int = 4) -> dict:
    """Operations and bytes of ONE dispatch over `rows` real query rows
    (`rows` 0: the per-dispatch bytes alone, as the reader asks)."""
    probed = NPROBE * n / NLIST               # rows a query scans, mean
    flops = rows * (2.0 * NLIST * d + 2.0 * probed * d)
    centroids = NLIST * d * 4
    one_pass = probed * (d * raw_bytes + 4 + 4)  # rows + sqnorm + id
    per_row = rows * (d * raw_bytes + r * (4 + 4))
    return {"flops": flops, "bytes": float(centroids + one_pass + per_row)}
