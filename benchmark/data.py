"""Corpus, query pool and the plain reference, all from `--seed`.

The generator is chip_smoke.py's (clustered Gaussians, queries = stored
rows + noise), copied here so that later PRs may change the program but
not the yardstick. The reference imports nothing of the program: exact
float64 top-k in numpy over the same rows, in blocks; under a filter,
over the rows that pass it.
"""

from __future__ import annotations

import numpy as np


GEN_BLOCK = 65536
THREADS = 8


def _pool():
    from concurrent.futures import ThreadPoolExecutor

    return ThreadPoolExecutor(THREADS)


def make_data(cfg: dict, seed: int, rows: int | None = None):
    """(base [n, d] f32, queries [pool, d] f32, query_rows [pool]).

    Rows are drawn in blocks of GEN_BLOCK, each from its own stream
    spawned off the seed, so that threads can fill them side by side and
    the same seed still gives the same rows."""
    g = cfg["data"]
    n, d = int(rows or cfg["rows"]), int(cfg["dimension"])
    rng = np.random.default_rng(seed)
    nc = max(min(int(g["centers"]), n // 200), 8)
    centers = (rng.standard_normal((nc, d)) * g["center_scale"]).astype(
        np.float32)
    which = rng.integers(0, nc, n)
    base = np.empty((n, d), np.float32)
    starts = list(range(0, n, GEN_BLOCK))
    streams = np.random.SeedSequence(seed).spawn(len(starts))

    def fill(i: int) -> None:
        lo = starts[i]
        blk = base[lo:lo + GEN_BLOCK]
        np.random.default_rng(streams[i]).standard_normal(
            blk.shape, dtype=np.float32, out=blk)
        blk *= np.float32(g["spread"])
        blk += centers[which[lo:lo + GEN_BLOCK]]

    with _pool() as ex:
        list(ex.map(fill, range(len(starts))))
    pool = min(int(g["query_pool"]), n)
    q_rows = rng.choice(n, pool, replace=False)
    queries = base[q_rows] + np.float32(g["query_noise"]) * rng.standard_normal(
        (pool, d), dtype=np.float32)
    return base, queries.astype(np.float32), q_rows


def scalar_column(field: dict, n: int) -> np.ndarray:
    """The configuration's scalar column: row i holds i % modulo."""
    return (np.arange(n) % int(field["modulo"])).astype(np.float64)


def normalise(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64)
    return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-30)


class ExactReference:
    """Exact top-k in float64. L2 scores are squared distances (smaller is
    nearer); Cosine scores are cosines (larger is nearer), rows and
    queries normalised as the configuration states.

    A float64 product of every query with every row is minutes of host
    time at 1M rows, and every run pays it. So each block of rows is
    first scored in float32 to choose `depth` candidates per query, far
    more than k, and only those are scored again in float64 and ranked:
    the answer is the exact one as long as float32 rounding (about 1e-4
    of a score) cannot move a true top-k row past `depth` others, which
    the generator's neighbour gaps rule out by orders of magnitude."""

    def __init__(self, base: np.ndarray, metric: str,
                 block_rows: int = 65536, depth: int = 64):
        if metric not in ("L2", "Cosine"):
            raise ValueError(f"reference has no metric {metric!r}")
        self.base, self.metric = base, metric
        self.block, self.depth = int(block_rows), int(depth)

    def _prep(self, x: np.ndarray) -> np.ndarray:
        return normalise(x) if self.metric == "Cosine" else x.astype(np.float64)

    def _keys(self, q: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """[Q, R] keys, smaller is nearer, in the dtype of `q`."""
        if self.metric == "L2":
            return (rows * rows).sum(1)[None, :] - 2.0 * (q @ rows.T)
        return -(q @ rows.T)

    def topk(self, queries: np.ndarray, k: int,
             allowed: np.ndarray | None = None) -> np.ndarray:
        """[Q, k] row ids, nearest first; with `allowed` ([n] bool), over
        the rows where it holds and no others."""
        if allowed is not None:
            rows = np.flatnonzero(allowed)
            if rows.size < k:
                raise ValueError(f"{rows.size} rows pass, fewer than k={k}")
            sub = ExactReference(self.base[rows], self.metric, self.block,
                                 self.depth)
            return rows[sub.topk(queries, k)]
        q64 = self._prep(queries)
        q32 = q64.astype(np.float32)
        n, depth = self.base.shape[0], max(self.depth, 4 * k)

        def candidates(lo: int) -> np.ndarray:
            blk = self.base[lo:lo + self.block]
            rows = (self._prep(blk).astype(np.float32)
                    if self.metric == "Cosine" else blk)
            key = self._keys(q32, rows)
            kk = min(depth, key.shape[1])
            return np.argpartition(key, kk - 1, axis=1)[:, :kk] + lo

        with _pool() as ex:
            cand = np.concatenate(
                list(ex.map(candidates, range(0, n, self.block))), 1)
        return self.rank(queries, cand, k)

    def rank(self, queries: np.ndarray, cand: np.ndarray,
             k: int) -> np.ndarray:
        """[Q, k]: the k nearest of each query's own candidate rows
        (cand [Q, C] row ids), scored in float64."""
        q64 = self._prep(queries)
        out = np.empty((q64.shape[0], k), np.int64)
        for i in range(q64.shape[0]):
            key = self._keys(q64[i:i + 1], self._prep(self.base[cand[i]]))[0]
            out[i] = cand[i][np.argsort(key, kind="stable")[:k]]
        return out

    def scores(self, queries: np.ndarray, q_idx: np.ndarray,
               ids: np.ndarray) -> np.ndarray:
        """The user-facing score the configuration promises for each
        (queries[q_idx[i]], base[ids[i]]) pair, exact in float64: squared
        L2 distance, or the cosine."""
        q = queries[q_idx].astype(np.float64)
        v = self.base[ids].astype(np.float64)
        if self.metric == "L2":
            return ((q - v) ** 2).sum(1)
        return (normalise(q) * normalise(v)).sum(1)


#: a column with more distinct values than this is answered set by set
MAX_VALUE_CLASSES = 256


def range_sets(values: np.ndarray, lo: np.ndarray,
               hi: np.ndarray) -> np.ndarray:
    """[n, 2]: for each range filter `lo <= column < hi`, the run
    [first, past-last) of the column's sorted distinct `values` that
    pass. Two filters with the same run pass the same rows."""
    return np.stack([np.searchsorted(values, lo, "left"),
                     np.searchsorted(values, hi, "left")], 1)


def range_truth(ref: ExactReference, queries: np.ndarray, k: int,
                col: np.ndarray, sets: np.ndarray) -> np.ndarray:
    """[S, Q, k]: the exact top-k under each of `sets` (rows of
    `range_sets` over np.unique(col)).

    A column of few distinct values (row i holds i % modulo) is answered
    value by value: the top-k of a union of rows lies in the union of
    each part's own exact top-k, so one pass over the corpus serves every
    set; the k x (values in the set) candidates are then ranked in
    float64. A column of many values is answered set by set."""
    values = np.unique(col)
    if values.size > MAX_VALUE_CLASSES:
        return np.stack([ref.topk(queries, k, allowed=(
            (col >= values[a]) & (col < (values[b] if b < values.size
                                         else np.inf))))
            for a, b in sets])
    used = sorted({v for a, b in sets for v in range(a, b)})
    own = {}
    for v in used:
        allowed = col == values[v]
        own[v] = ref.topk(queries, min(k, int(allowed.sum())), allowed)
    out = []
    for a, b in sets:
        cand = np.concatenate([own[v] for v in range(a, b)] or
                              [np.empty((queries.shape[0], 0), np.int64)], 1)
        if cand.shape[1] < k:
            raise ValueError(f"{cand.shape[1]} rows pass values "
                             f"[{a}, {b}), fewer than k={k}")
        out.append(ref.rank(queries, cand, k))
    return np.stack(out)


def recall_rows(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per-row recall@k: got [R, k] ids (-1 = missing), want [R, k]."""
    hit = (got[:, :, None] == want[:, None, :]).any(2)
    return hit.sum(1) / want.shape[1]
