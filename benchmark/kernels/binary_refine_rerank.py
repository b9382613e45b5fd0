"""Needed work of the three-stage refinement program (`ops/binary_scan.py`
`binary_refine_rerank`, XLA module `jit_binary_refine_rerank`), from
shapes alone.

Needed work is the algorithm's, whatever implements it: every query row
is scored against the sign bits of every stored row (stage 0:
2*rows*N*d operations), its `r0` survivors against their int8 rows
(stage 1: 2*rows*r0*d) and its `r1` survivors against their raw rows
(stage 2: 2*rows*r1*d). `r1` is the `rerank` the reader hands over as
`r`; `r0` is the configuration's (`configs/gist1m-960-ivfrabitq.json`:
`search.index_params.r0` if a request sends one, else `serving.r0`, the
product's default).

Which bound was taken, and why. It must hold for ANY implementation of
the same semantics, so that no later PR can read over 100 %:
- operations at the bf16 peak: stage 0's product is of +-1 values and
  could run narrower, stage 2 promises float32 at `highest` and runs
  wider; the published peak has neither column, and a lower bound may
  be generous;
- bytes per DISPATCH: the bit planes, one bit a dimension (N*d/8), the
  two f32 per-row columns of stage 0 and the validity mask, read once;
- bytes per ROW: the query, `r0` int8 rows with their two columns,
  `r1` raw rows with their norm, and the row's r (score, id) results.
Not needed work: the unpacked +-1 operand ([N, d] bf16, 16x the planes),
the [rows, N] f32 score matrix stage 0's selection reads, the super-row
a gather fetches beside the row it wants at a width that is no multiple
of 128, any relayout. A later PR may remove them without this
yardstick moving.

At 1,000,000 x 960, 64 rows: 122.97 GFLOP -> 0.624 ms at 197 TFLOP/s
against 0.129 GB a dispatch + 0.095 GB of gathered rows -> 0.273 ms at
819 GB/s: compute-bound, and it cannot pass 100.
"""

from __future__ import annotations

import json
import os

from benchmark.kernels.int8_scan_rerank import least_seconds  # noqa: F401

#: how the program appears among the device trace's XLA modules
MODULE_SUBSTRING = "binary_refine_rerank"

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs",
        "gist1m-960-ivfrabitq.json")) as _f:
    _CFG = json.load(_f)
R0 = int(_CFG["search"]["index_params"].get("r0", _CFG["serving"]["r0"]))


def needed(rows: int, n: int, d: int, r: int, raw_bytes: int = 4) -> dict:
    """Operations and bytes of ONE dispatch over `rows` real query rows
    (`rows` 0: the per-dispatch bytes alone, as the reader asks); `r` is
    r1, the depth of the exact rerank."""
    r0 = max(R0, r)
    flops = rows * 2.0 * d * (n + r0 + r)
    once = n * d / 8 + 2 * 4 * n + n        # planes, two columns, mask
    per_row = rows * (d * 4 + r0 * (d + 2 * 4)
                      + r * (d * raw_bytes + 4) + r * (4 + 4))
    return {"flops": flops, "bytes": float(once + per_row)}
