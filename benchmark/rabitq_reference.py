"""The plain reference of `gist1m-960-ivfrabitq`: the three-stage
refinement funnel in straightforward numpy and float64. It imports
nothing of the program and no jax.

The funnel, as the configuration's `serving.funnel` states it and
upstream's `gamma_index_ivfrabitq.cc` (faiss `IndexIVFRaBitQ`: estimate
from 1-bit codes, then re-rank) runs it, for squared L2:

    stage 0  every allowed row is scored from its sign bits alone:
             row ~ s * sign(row), s = mean |row| (the first-order form
             of RaBitQ's estimator), so
             |q - row|^2 ~ |q|^2 - 2 s (q . sign(row)) + d s^2;
             the r0 rows of least estimate survive.
    stage 1  the survivors are scored against the reconstruction
             centroid + t * sign(row - centroid), t = mean |row -
             centroid|, centroid the row's own list's, stored as int8
             (255 levels of the row's largest magnitude); the r1 of
             least distance survive.
    stage 2  the survivors are scored exactly; the k nearest are served.

The centroids and the assignment of rows to lists are the index's own
(training is k-means, seeded and iterated: what it finds is no part of
the semantics); everything after them is recomputed here. The program
runs stages 0 and 1 as bfloat16 products (the query rounded to 8 bits
of mantissa): where its answer differs from this file's, the row it
lost sits within that rounding of a stage's cut (`margins` says how
near).

`benchmark/check.py` decides a run's `correct` against the exact top-k
over ALL rows (`data.ExactReference`): what a user of any index is
owed. This file is what the CPU tests hold the served path to
(tests/test_rabitq_served_cell.py), and what reads a recall miss:
`lost_at` names the stage that dropped a true neighbour.
"""

from __future__ import annotations

import numpy as np


def sign_code(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(signs [n, d] in {-1, +1}, scale [n]) of the 1-bit code: a
    dimension that is not positive codes as -1, as a packed 0 bit."""
    rows = rows.astype(np.float64)
    return (np.where(rows > 0.0, 1.0, -1.0),
            np.maximum(np.abs(rows).mean(axis=1), 1e-12))


def stage0_estimates(base: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """[Q, N] estimated squared distances from the sign bits."""
    signs, scale = sign_code(base)
    q = queries.astype(np.float64)
    d = base.shape[1]
    return ((q * q).sum(1)[:, None] - 2.0 * (q @ signs.T) * scale[None, :]
            + d * scale[None, :] ** 2)


def reconstruction(base: np.ndarray, centroids: np.ndarray,
                   lists: np.ndarray, int8: bool = True) -> np.ndarray:
    """[N, d] stage 1's view of every row: its list's centroid plus the
    signs of the residual at the residual's mean magnitude, stored as
    the configuration states (`int8`; False: unrounded)."""
    cent = centroids.astype(np.float64)[lists]
    resid = base.astype(np.float64) - cent
    t = np.maximum(np.abs(resid).mean(axis=1), 1e-12)
    recon = cent + t[:, None] * np.sign(resid)
    return as_int8(recon) if int8 else recon


def as_int8(rows: np.ndarray) -> np.ndarray:
    """[N, d] the rows as the configuration stores stage 1's (`int8`):
    each element rounded to the nearest of 255 levels, -127..127 times
    the row's largest magnitude / 127."""
    step = np.maximum(np.abs(rows).max(axis=1) / 127.0, 1e-12)[:, None]
    return np.clip(np.rint(rows / step), -127, 127) * step


def sq_dists_to(queries: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """[Q, C] squared distances of queries[i] to rows[i, j], by the
    difference itself."""
    diff = queries.astype(np.float64)[:, None, :] - rows.astype(np.float64)
    return np.einsum("qcd,qcd->qc", diff, diff)


def _least(keys: np.ndarray, ids: np.ndarray, keep: int) -> np.ndarray:
    """[Q, keep] the ids of the `keep` least keys of each row, least
    first, -1 where fewer are finite."""
    order = np.argsort(keys, axis=1, kind="stable")[:, :keep]
    out = np.take_along_axis(ids, order, 1)
    out[~np.isfinite(np.take_along_axis(keys, order, 1))] = -1
    return out


def funnel(base: np.ndarray, centroids: np.ndarray, lists: np.ndarray,
           queries: np.ndarray, r0: int, r1: int, k: int,
           allowed: np.ndarray | None = None, skip_stage1: bool = False
           ) -> dict:
    """The three stages. Returns {"stage0": [Q, r0] ids, "stage1":
    [Q, r1] ids, "ids": [Q, k], "dists": [Q, k] exact squared
    distances, nearest first}; -1 / inf where a stage holds fewer rows.
    `allowed` ([N] bool) leaves out deleted and filtered rows before
    stage 0; `skip_stage1` hands stage 0's first r1 straight to the
    exact stage (a funnel the program must NOT be)."""
    n = base.shape[0]
    r0, r1 = min(r0, n), min(r1, n)
    est = stage0_estimates(base, queries)
    if allowed is not None:
        est[:, ~allowed] = np.inf
    all_ids = np.broadcast_to(np.arange(n), est.shape)
    s0 = _least(est, all_ids, r0)
    if skip_stage1:
        s1 = s0[:, :r1].copy()
    else:
        recon = reconstruction(base, centroids, lists)
        d1 = sq_dists_to(queries, recon[np.maximum(s0, 0)])
        d1[s0 < 0] = np.inf
        s1 = _least(d1, s0, r1)
    d2 = sq_dists_to(queries, base[np.maximum(s1, 0)])
    d2[s1 < 0] = np.inf
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    ids = np.take_along_axis(s1, order, 1)
    return {"stage0": s0, "stage1": s1, "ids": ids,
            "dists": np.take_along_axis(d2, order, 1)}


def margins(base: np.ndarray, centroids: np.ndarray, lists: np.ndarray,
            query: np.ndarray, row: int, r0: int, r1: int,
            allowed: np.ndarray | None = None) -> dict:
    """How near `row` sits to each stage's cut for ONE query, as shares
    of the size of the terms the score cancels (|q|^2 + |row|^2):
    {"stage0": (its estimate - the r0-th least estimate) / size,
    "stage1": likewise among stage 0's survivors (None if it was not
    one)}. Negative: inside the cut; within 1e-3 of zero a bfloat16
    product may put it on either side (the program's stage-0 estimate
    sits within 2e-4 of this file's on 960-d rows)."""
    q = query.astype(np.float64)[None]
    size = float((q * q).sum() + (base[row].astype(np.float64) ** 2).sum())
    est = stage0_estimates(base, q)[0]
    if allowed is not None:
        est[~allowed] = np.inf
    cut0 = np.sort(est)[min(r0, est.size) - 1]
    out = {"stage0": float((est[row] - cut0) / size), "stage1": None}
    s0 = np.argsort(est, kind="stable")[:r0]
    if row in s0:
        recon = reconstruction(base[s0], centroids, lists[s0])
        d1 = sq_dists_to(q, recon[None])[0]
        cut1 = np.sort(d1)[min(r1, d1.size) - 1]
        out["stage1"] = float((d1[list(s0).index(row)] - cut1) / size)
    return out


def lost_at(base: np.ndarray, centroids: np.ndarray, lists: np.ndarray,
            query: np.ndarray, row: int, r0: int, r1: int,
            allowed: np.ndarray | None = None) -> str:
    """Which stage of THIS funnel drops `row` for `query`: "stage0",
    "stage1" or "kept" (it reaches the exact stage)."""
    m = margins(base, centroids, lists, query, row, r0, r1, allowed)
    if m["stage0"] > 0:
        return "stage0"
    if m["stage1"] is None or m["stage1"] > 0:
        return "stage1"
    return "kept"
