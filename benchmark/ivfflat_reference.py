"""The plain reference of `sift1m-ivfflat`: IVF-Flat in straightforward
numpy and float64. It imports nothing of the program and no jax.

IVF-Flat, as faiss's IndexIVFFlat and upstream's gamma_index_ivfflat
state it: given trained centroids, every stored row belongs to the list
of its nearest centroid (squared L2); a query is answered from the
`nprobe` lists whose centroids are nearest to it, by the exact top-k
over the rows of those lists and no others. The centroids are the
index's own (training is k-means, seeded and iterated: what it finds is
no part of the semantics); everything after them is recomputed here.

`benchmark/check.py` decides a run's `correct` against the exact top-k
over ALL rows (`data.ExactReference`): what a user of any index is
owed. This file is what the CPU tests hold the served path to, id for
id (tests/test_ivfflat_served_cell.py): the same lists probed, every
probed row scored, nothing dropped.
"""

from __future__ import annotations

import numpy as np

#: float64 elements of one block of differences (64 MB)
BLOCK_ELEMENTS = 8_000_000


def sq_dists(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """[A, B] squared L2 distances, float64, by the difference itself
    (no |a|^2 - 2ab + |b|^2: nothing cancels), a block of `a` at a
    time."""
    a, b = a.astype(np.float64), b.astype(np.float64)
    out = np.empty((a.shape[0], b.shape[0]))
    step = max(BLOCK_ELEMENTS // max(b.size, 1), 1)
    for lo in range(0, a.shape[0], step):
        diff = a[lo:lo + step, None, :] - b[None, :, :]
        out[lo:lo + step] = np.einsum("abd,abd->ab", diff, diff)
    return out


def assign(base: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """[N] the list of each row: its nearest centroid (the lowest index
    of equals)."""
    return np.concatenate([
        sq_dists(base[lo:lo + 4096], centroids).argmin(1)
        for lo in range(0, base.shape[0], 4096)])


def probes(queries: np.ndarray, centroids: np.ndarray,
           nprobe: int) -> np.ndarray:
    """[Q, nprobe] the lists a query probes, nearest centroid first."""
    return np.argsort(sq_dists(queries, centroids), axis=1,
                      kind="stable")[:, :nprobe]


def search(base: np.ndarray, centroids: np.ndarray, queries: np.ndarray,
           nprobe: int, k: int, lists: np.ndarray | None = None,
           allowed: np.ndarray | None = None,
           probed: np.ndarray | None = None
           ) -> tuple[np.ndarray, np.ndarray]:
    """(ids [Q, k], squared distances [Q, k]), nearest first, -1 / inf
    where the probed lists hold fewer than k rows. `lists` is
    `assign(base, centroids)` if the caller has it already; `allowed`
    ([N] bool) leaves out deleted and filtered rows; `probed` ([Q, p],
    -1 = no list) takes the place of the nearest-centroid choice."""
    if lists is None:
        lists = assign(base, centroids)
    if probed is None:
        probed = probes(queries, centroids, nprobe)
    live = np.ones(base.shape[0], bool) if allowed is None else allowed
    ids = np.full((queries.shape[0], k), -1, np.int64)
    dists = np.full((queries.shape[0], k), np.inf)
    for i, q in enumerate(queries):
        rows = np.flatnonzero(np.isin(lists, probed[i][probed[i] >= 0])
                              & live)
        d = sq_dists(q[None], base[rows])[0]
        order = np.argsort(d, kind="stable")[:k]
        ids[i, :order.size], dists[i, :order.size] = rows[order], d[order]
    return ids, dists
