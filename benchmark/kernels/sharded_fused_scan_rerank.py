"""Needed work of the mesh-spanning fused scan + exact rerank
(`parallel/sharded.py` `_ivf_search_fn`, XLA module
`jit_sharded_fused_scan_rerank`), from shapes alone.

The algorithm's work is `int8_scan_rerank`'s (that file's docstring):
every query row against every stored row, r candidates rescored
exactly; the mirror read once per dispatch, r raw rows gathered per
query. What a mesh changes is how the reader
(`benchmark/metrics/int8_scan_rerank_roofline.py`) meets it in the
trace, and this file is written to that reader:

- it sums the device time of the program's events over ALL chips'
  planes: one dispatch is SHARDS events, and the time is chip-seconds;
- it charges `needed(0, ...)["bytes"]` once per EVENT, so that term is
  ONE shard's slice of the mirror ((N / SHARDS) * (d + 8)): SHARDS
  events a dispatch then read the mirror once;
- it charges the per-row terms once per real query row: operations are
  the whole mesh's (2*N*d + 2*r*d a row: the shards split the stored
  rows, not the work of a row), bytes are r raw rows plus the query,
  once (each candidate is owned by one shard; that every chip gathers r
  rows and keeps its own is the program's way, not needed work).

Least time at ONE chip's peaks over summed chip-seconds is then the
mesh's share of SHARDS chips' roofline: it cannot pass 100 %. The
collectives (all_gather of SHARDS * r candidates, pmax of r scores: some
0.6 MB a 64-row dispatch) are latency, not bandwidth, and are not
needed work; `mesh_collective_pct` reads what they cost.
"""

from __future__ import annotations

from benchmark.kernels.int8_scan_rerank import least_seconds  # noqa: F401

#: how the program appears among the device trace's XLA modules
MODULE_SUBSTRING = "sharded_fused_scan_rerank"
#: the configuration's mesh: 4 x 1 (data x query), one v5e-4 host
SHARDS = 4


def needed(rows: int, n: int, d: int, r: int, raw_bytes: int = 4) -> dict:
    """Operations and bytes of one EVENT (one chip's part of a dispatch)
    as the reader adds them up: the per-dispatch term is one shard's,
    the per-row terms are the whole mesh's and come once a row."""
    flops = 2.0 * rows * n * d + 2.0 * rows * r * d
    mirror_shard = (n // SHARDS) * (d + 2 * 4)  # int8 rows + scale + sqnorm
    gathered = rows * r * (d * raw_bytes + 4)
    queries = rows * d * 4
    return {"flops": flops, "bytes": float(mirror_shard + gathered + queries)}
