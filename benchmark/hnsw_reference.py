"""The plain reference of `gist1m-960-hnsw`: HNSW's device scan in
straightforward numpy and float64. It imports nothing of the program and
no jax.

What the configuration's `serving.path` states, for cosine:

    1. rows and queries are normalised to unit length;
    2. every row is stored as int8 by the product's documented rule
       (`index/int8_mirror.py` `quantize_rows`): a per-row scale
       s = max |x| / 127, each element rint(x / s) clipped to +-127;
    3. the int8 stage: every allowed row is scored
       score(q, row) = (bf16(q) . q8_row) * s_row, the query rounded to
       bfloat16 (8 significant bits, to nearest even) as the product's
       scan rounds it before its product (the int8 levels are exact in
       bfloat16); the r = min(efSearch, rows) best survive;
    4. those r rows are reranked by their exact cosine against the raw
       rows as stored; the k best are served with that cosine.

Under L2 step 1 is skipped and step 3 scores
-(|q|^2 - 2 (bf16(q) . q8_row) s_row + |s_row q8_row|^2).

Departures from the product, each able to move only a row at a cut:
the products here accumulate in float64 (the program: float32); rows
are normalised and quantised from float64 (the program: float32, so an
element within float32 rounding of a half level can take the other
level); rows of equal score are taken by row id (the program's order of
equal scores is its selection's).

`benchmark/check.py` decides a run's `correct` against the exact top-k
over ALL rows (`data.ExactReference`): what a user of any index is
owed. This file is what the CPU tests hold the served path to
(tests/test_hnsw_served_cell.py), and what the window's answers are
held to on the chip, in blocks of rows at the timed sizes.
"""

from __future__ import annotations

import numpy as np

#: rows scored at a time: a block's float64 copies stay small
BLOCK_ROWS = 65536


def normalise(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64)
    return x / np.maximum(np.linalg.norm(x, axis=1, keepdims=True), 1e-15)


def quantize(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(levels [n, d] in -127..127, scale [n]) of rows stored as int8."""
    rows = rows.astype(np.float64)
    scale = np.maximum(np.abs(rows).max(axis=1) / 127.0, 1e-12)
    return np.clip(np.rint(rows / scale[:, None]), -127, 127), scale


def bf16(x: np.ndarray) -> np.ndarray:
    """x rounded to bfloat16 (8 significant bits, to nearest even), as
    float64."""
    bits = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


def scan_scores(rows: np.ndarray, queries: np.ndarray, metric: str,
                normalised: bool = True) -> np.ndarray:
    """[Q, n] int8-stage scores of `rows` (larger is nearer). `normalised`
    False scores the rows and queries as given (a scan that skipped the
    normalisation: a program this must NOT be)."""
    cos = metric == "Cosine" and normalised
    r = normalise(rows) if cos else rows.astype(np.float64)
    q = normalise(queries) if cos else queries.astype(np.float64)
    levels, scale = quantize(r)
    dots = (bf16(q) @ levels.T) * scale[None, :]
    if metric != "L2":
        return dots
    deq = levels * scale[:, None]
    return -((q * q).sum(1)[:, None] - 2.0 * dots
             + (deq * deq).sum(1)[None, :])


def exact_scores(base: np.ndarray, queries: np.ndarray, ids: np.ndarray,
                 metric: str) -> np.ndarray:
    """[Q, C] the served score of queries[i] against base[ids[i, j]]:
    the cosine, or the squared L2 distance (smaller is nearer)."""
    v = base[np.maximum(ids, 0)].astype(np.float64)
    q = queries.astype(np.float64)[:, None, :]
    if metric == "L2":
        return ((q - v) ** 2).sum(2)
    num = (q * v).sum(2)
    return num / np.maximum(np.linalg.norm(q, axis=2)
                            * np.linalg.norm(v, axis=2), 1e-30)


def search(base: np.ndarray, queries: np.ndarray, ef: int, k: int,
           metric: str = "Cosine", allowed: np.ndarray | None = None,
           rerank: bool = True, normalised: bool = True) -> dict:
    """The device scan of HNSW over `base`, in blocks of rows. Returns
    {"candidates": [Q, r] ids, best first by the int8 stage, "ids":
    [Q, k], "scores": [Q, k], best first}; -1 / -inf (L2: inf) where
    fewer rows are allowed. `allowed` ([n] bool) leaves out deleted and
    filtered rows before the int8 stage. `rerank` False serves the int8
    stage's own best k with its own scores, and `normalised` False scans
    without the normalisation: programs this must NOT be."""
    n = base.shape[0]
    r = min(ef, n)
    best_s = np.full((queries.shape[0], 0), -np.inf)
    best_i = np.zeros((queries.shape[0], 0), np.int64)
    for lo in range(0, n, BLOCK_ROWS):
        s = scan_scores(base[lo:lo + BLOCK_ROWS], queries, metric,
                        normalised)
        if allowed is not None:
            s[:, ~allowed[lo:lo + BLOCK_ROWS]] = -np.inf
        ids = np.broadcast_to(np.arange(lo, lo + s.shape[1]), s.shape)
        cat_s = np.concatenate([best_s, s], 1)
        cat_i = np.concatenate([best_i, ids], 1)
        # best first; of equal scores the lower row id
        order = np.lexsort((cat_i, -cat_s), axis=1)[:, :r]
        best_s = np.take_along_axis(cat_s, order, 1)
        best_i = np.take_along_axis(cat_i, order, 1)
    # fewer than k candidates (r < k) leave the answer's last places empty
    pad = max(k - r, 0)
    best_s = np.pad(best_s, ((0, 0), (0, pad)), constant_values=-np.inf)
    best_i = np.pad(best_i, ((0, 0), (0, pad)))
    cand = np.where(np.isfinite(best_s), best_i, -1)
    if not rerank:
        ids, scores = cand[:, :k], best_s[:, :k]
        return {"candidates": cand[:, :r], "ids": ids,
                "scores": -scores if metric == "L2" else scores}
    exact = exact_scores(base, queries, cand, metric)
    key = exact if metric == "L2" else -exact
    key = np.where(cand >= 0, key, np.inf)
    order = np.lexsort((cand, key), axis=1)[:, :k]
    ids = np.take_along_axis(cand, order, 1)
    scores = np.take_along_axis(exact, order, 1)
    scores[ids < 0] = np.inf if metric == "L2" else -np.inf
    return {"candidates": cand[:, :r], "ids": ids, "scores": scores}
