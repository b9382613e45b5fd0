"""The control: the reference put in the program's place, computed in the
nearest precision below the one the configuration states. The
configuration promises a float32 rerank at `highest`; the step below is
`high` (three bfloat16 passes). The control's answers for the window's
queries go through the same comparison as the program's, and have to
come out as not correct. A benchmark run does not run this; `--control`
does, after the window, on the chip.
"""

from __future__ import annotations

import json
import sys

import numpy as np

from benchmark import check


def scores_at(cfg: dict, queries: np.ndarray, rows: np.ndarray,
              precision: str) -> np.ndarray:
    """Scores of queries[i] against rows[i, j] ([Q, d] x [Q, k, d]),
    float32 on the default device at the given matmul precision. One
    plain [Q, d] x [d, Q*k] product, so that it runs on the matrix unit
    (a product batched per query is lowered to exact float32 vector
    arithmetic whatever precision it asks for)."""
    import jax
    import jax.numpy as jnp

    nq, k, d = rows.shape
    q = jnp.asarray(queries, jnp.float32)
    v = jnp.asarray(rows.reshape(nq * k, d), jnp.float32)
    full = jnp.matmul(q, v.T, precision=getattr(jax.lax.Precision, precision),
                      preferred_element_type=jnp.float32)
    dots = full.reshape(nq, nq, k)[jnp.arange(nq), jnp.arange(nq)]
    q2, v2 = (q * q).sum(1)[:, None], (v * v).sum(1).reshape(nq, k)
    if cfg["metric"] == "L2":
        return np.asarray(q2 - 2.0 * dots + v2, np.float64)
    return np.asarray(dots / jnp.sqrt(q2 * v2), np.float64)


def answers(cfg, ref, queries, truth, precision: str = "HIGH"):
    """The control's ids and scores for every pool query: the reference's
    own top-k rows, scored in the lower precision and ranked by it."""
    s = scores_at(cfg, queries, ref.base[truth], precision)
    order = np.argsort(s if cfg["metric"] == "L2" else -s, axis=1,
                       kind="stable")
    return (np.take_along_axis(truth, order, 1),
            np.take_along_axis(s, order, 1))


def report(cfg, ref, queries, truth, win, filtered=None) -> None:
    """Print the control's checks beside the program's (stderr). Under a
    filter (`filtered`: run.py's FilteredTruth) the control answers each
    request from the truth of its own passing set."""
    q_idx = win["q_idx"]
    filt = None
    if filtered is not None:
        truth, filt = filtered.of(win["f_lo"], win["f_width"])
    for precision in ("HIGHEST", "HIGH", "DEFAULT"):
        if filt is None:
            ids, scores = answers(cfg, ref, queries, truth, precision)
            ids, scores = ids[q_idx], scores[q_idx]
        else:
            per_set = [answers(cfg, ref, queries, t, precision)
                       for t in truth]
            sets = filt["set_idx"][:, None]
            ids = np.stack([a[0] for a in per_set])[sets, q_idx]
            scores = np.stack([a[1] for a in per_set])[sets, q_idx]
        checks, _ = check.compare(cfg, ref, queries, truth, q_idx,
                                  ids, scores, filt)
        print(json.dumps({"control": precision, "checks": checks,
                          "correct": all(check.passed(c)
                                         for c in checks.values())}),
              file=sys.stderr, flush=True)
