"""Needed work of the fused compressed scan + exact rerank
(`ops/ivf.py` `int8_scan_rerank`), from shapes alone.

Needed work is the algorithm's, whatever implements it: every query row
is scored against every stored row (2*rows*N*d operations, at the bf16
peak: the program casts the int8 mirror to bf16 for the MXU) and its r
candidates are rescored exactly (2*rows*r*d). Bytes: the int8 mirror
with its two f32 per-row columns is read once per dispatch, and r raw
rows are gathered per query. The [rows, N] f32 score matrix the current
program writes and re-reads is an artefact, not needed work: a later PR
may remove it without this yardstick moving.
"""

from __future__ import annotations

#: how the program appears among the device trace's XLA modules
MODULE_SUBSTRING = "int8_scan_rerank"


def needed(rows: int, n: int, d: int, r: int, raw_bytes: int = 4) -> dict:
    """Operations and bytes of ONE dispatch over `rows` real query rows."""
    flops = 2.0 * rows * n * d + 2.0 * rows * r * d
    mirror = n * d + 2 * 4 * n            # int8 rows + scale + sqnorm
    gathered = rows * r * (d * raw_bytes + 4)
    queries = rows * d * 4
    return {"flops": flops, "bytes": float(mirror + gathered + queries)}


def least_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound binds."""
    t_compute = work["flops"] / peak["bf16_flops_per_s"]
    t_memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
