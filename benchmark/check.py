"""What decides `correct`: the answers the timed requests themselves
returned, against the plain reference. Each number compared has a limit
of its own (PERF.md gives the readings each was set from)."""

from __future__ import annotations

import json
import sys

import numpy as np

from benchmark import data


def score_error(cfg: dict, ref: data.ExactReference, queries: np.ndarray,
                q_idx: np.ndarray, ids: np.ndarray,
                scores: np.ndarray) -> float:
    """Widest gap between a served score and the reference's score of the
    same (query, row) pair, over every pair the window served. L2 gaps
    are measured against |q|^2 + |v|^2, the size of the terms the
    distance cancels; a cosine is already of size 1."""
    pairs = np.stack([q_idx.ravel(), ids.ravel()], 1)
    keep = pairs[:, 1] >= 0
    pairs, first = np.unique(pairs[keep], axis=0, return_index=True)
    served = scores.ravel()[keep][first]
    if pairs.shape[0] == 0:
        return float("inf")
    want = ref.scores(queries, pairs[:, 0], pairs[:, 1])
    gap = np.abs(served - want)
    if cfg["metric"] == "L2":
        q2 = (queries.astype(np.float64) ** 2).sum(1)[pairs[:, 0]]
        v2 = (ref.base[pairs[:, 1]].astype(np.float64) ** 2).sum(1)
        gap = gap / (q2 + v2)
    return float(np.nanmax(np.where(np.isnan(gap), np.inf, gap)))


def filter_violations(filt: dict, ids: np.ndarray) -> int:
    """Served ids ([n, rows, k], -1 = no hit) whose column value does not
    satisfy their own request's `lo <= value < hi`."""
    value = filt["col"][np.maximum(ids, 0)]
    lo, hi = filt["lo"][:, None, None], filt["hi"][:, None, None]
    return int(((ids >= 0) & ~((value >= lo) & (value < hi))).sum())


def compare(cfg: dict, ref: data.ExactReference, queries: np.ndarray,
            truth: np.ndarray, q_idx: np.ndarray, ids: np.ndarray,
            scores: np.ndarray, filt: dict | None = None
            ) -> tuple[dict, np.ndarray]:
    """Checks on the window's answers: q_idx [n, rows] pool rows asked,
    ids/scores [n, rows, k] served; truth [pool, k]. Under a filter,
    `filt` holds per request its bounds `lo`, `hi` [n] and the passing
    set it asked for (`set_idx` [n]), the column `col` [N], and truth is
    [S, pool, k]: each served row is held to the truth of its own
    request's passing set. Returns (checks, per-row recall)."""
    lim = cfg["limits"]
    k = truth.shape[-1]
    flat_ids = ids.reshape(-1, k)
    flat_q = np.repeat(q_idx.ravel(), k).reshape(-1, k)
    if filt is None:
        want = truth[q_idx.ravel()]
    else:
        want = truth[np.repeat(filt["set_idx"], q_idx.shape[1]),
                     q_idx.ravel()]
    rec = (data.recall_rows(flat_ids, want)
           if flat_ids.size else np.zeros(0))
    short = int((flat_ids < 0).any(1).sum())
    checks = {
        "answers_compared": {"value": int(flat_ids.shape[0]), "limit": 1,
                             "op": ">="},
        "recall_at_10": {"value": float(rec.mean()) if rec.size else 0.0,
                         "limit": lim["recall_at_10_min"], "op": ">="},
        "short_rows": {"value": short, "limit": 0, "op": "<="},
        "score_err": {"value": score_error(cfg, ref, queries, flat_q,
                                           flat_ids, scores.reshape(-1, k)),
                      "limit": lim["score_err_max"], "op": "<="},
    }
    if filt is not None:
        checks["filter_violations"] = {
            "value": filter_violations(filt, ids), "limit": 0, "op": "<="}
    return checks, rec


def passed(check: dict) -> bool:
    v, lim = check["value"], check["limit"]
    if isinstance(v, float) and np.isnan(v):
        return False
    return v >= lim if check["op"] == ">=" else v <= lim


def report(checks: dict) -> bool:
    """Print each number compared beside its limit, last on stderr."""
    ok = all(passed(c) for c in checks.values())
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} {c['op']} {c['limit']!r} "
              f"{'ok' if passed(c) else 'FAILED'}", file=sys.stderr)
    print(f"correct: {json.dumps(ok)}", file=sys.stderr, flush=True)
    return ok
