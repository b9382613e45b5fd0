"""Generator, plain reference and recall arithmetic (benchmark/data.py),
and the comparison that decides `correct` (benchmark/check.py)."""

import json
import os

import numpy as np
import pytest

from benchmark import check, control, data

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def small_cfg(metric="L2", d=32):
    with open(os.path.join(REPO, "benchmark", "configs",
                           "sift1m-ivfpq.json")) as f:
        cfg = json.load(f)
    cfg.update(rows=3000, dimension=d, metric=metric)
    cfg["data"]["query_pool"] = 24
    return cfg


def plain_topk(base, queries, k, metric):
    b, q = base.astype(np.float64), queries.astype(np.float64)
    if metric == "Cosine":
        b, q = data.normalise(b), data.normalise(q)
        key = -(q @ b.T)
    else:
        key = ((q[:, None, :] - b[None, :, :]) ** 2).sum(2)
    return np.argsort(key, axis=1, kind="stable")[:, :k]


def test_same_seed_same_rows_and_a_large_seed_works():
    cfg = small_cfg()
    a = data.make_data(cfg, 3_000_000_001 % 2 ** 32)
    b = data.make_data(cfg, 3_000_000_001 % 2 ** 32)
    c = data.make_data(cfg, 5)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == (3000, 32) and a[0].dtype == np.float32
    assert a[1].shape == (24, 32) and len(set(a[2].tolist())) == 24


@pytest.mark.parametrize("metric", ["L2", "Cosine"])
def test_blocked_reference_equals_the_plain_float64_topk(metric):
    cfg = small_cfg(metric)
    base, queries, q_rows = data.make_data(cfg, 11)
    ref = data.ExactReference(base, metric, block_rows=700, depth=16)
    got = ref.topk(queries, 10)
    np.testing.assert_array_equal(got, plain_topk(base, queries, 10, metric))
    assert (got[:, 0] == q_rows).all()  # a query's nearest row is its source


@pytest.mark.parametrize("width", [1, 30], ids=["pass2pct", "pass60pct"])
@pytest.mark.parametrize("metric", ["L2", "Cosine"])
def test_filtered_reference_equals_the_plain_topk_over_passing_rows(
        metric, width, monkeypatch):
    """`lo <= price < lo + width` of `i % 50`: the plain float64 top-k
    over the passing rows alone, by `topk(allowed=)` and by
    `range_truth`'s two ways (value by value; set by set)."""
    cfg = small_cfg(metric)
    base, queries, _ = data.make_data(cfg, 13)
    col = data.scalar_column(cfg["scalar_columns"][0], base.shape[0])
    lo = 7.25
    allowed = (col >= lo) & (col < lo + width)
    assert allowed.mean() == width / 50
    rows = np.flatnonzero(allowed)
    want = rows[plain_topk(base[rows], queries, 10, metric)]
    ref = data.ExactReference(base, metric, block_rows=700, depth=16)
    np.testing.assert_array_equal(ref.topk(queries, 10, allowed), want)
    sets = data.range_sets(np.unique(col), np.array([lo]),
                           np.array([lo + width]))
    assert sets.tolist() == [[8, 8 + width]]
    np.testing.assert_array_equal(
        data.range_truth(ref, queries, 10, col, sets)[0], want)
    monkeypatch.setattr(data, "MAX_VALUE_CLASSES", 4)
    np.testing.assert_array_equal(
        data.range_truth(ref, queries, 10, col, sets)[0], want)


def test_a_filter_that_passes_fewer_rows_than_k_is_an_error():
    base = np.random.default_rng(0).standard_normal((40, 4)).astype(np.float32)
    ref = data.ExactReference(base, "L2")
    col = (np.arange(40) % 10).astype(np.float64)
    with pytest.raises(ValueError):
        ref.topk(base[:2], 10, allowed=col == 3)
    with pytest.raises(ValueError):
        data.range_truth(ref, base[:2], 10, col, np.array([[3, 4]]))
    assert data.range_truth(ref, base[:2], 10, col,
                            np.array([[3, 6]])).shape == (1, 2, 10)


def test_reference_scores_are_what_the_configuration_promises():
    base = np.array([[0, 0], [3, 4], [1, 0]], np.float32)
    q = np.array([[0, 0], [1, 0]], np.float32)
    l2 = data.ExactReference(base, "L2").scores(q, np.array([0, 1]),
                                                np.array([1, 1]))
    np.testing.assert_allclose(l2, [25.0, 20.0])
    cos = data.ExactReference(base, "Cosine").scores(q, np.array([1, 1]),
                                                     np.array([1, 2]))
    np.testing.assert_allclose(cos, [0.6, 1.0])


def test_unknown_metric_is_an_error():
    with pytest.raises(ValueError):
        data.ExactReference(np.zeros((4, 2), np.float32), "Hamming")


def test_recall_rows_by_hand():
    want = np.array([[1, 2, 3, 4], [5, 6, 7, 8]])
    got = np.array([[4, 3, 9, -1], [5, 6, 7, 8]])
    np.testing.assert_allclose(data.recall_rows(got, want), [0.5, 1.0])


def served(cfg, ref, queries, truth, q_idx):
    """What a sound program serves: the reference's ids with float32
    scores."""
    ids = truth[q_idx]
    flat_q = np.repeat(q_idx.ravel(), truth.shape[1])
    scores = ref.scores(queries, flat_q, ids.ravel()).astype(
        np.float32).reshape(ids.shape)
    return ids, scores.astype(np.float64)


@pytest.fixture(scope="module")
def world():
    cfg = small_cfg()
    base, queries, _ = data.make_data(cfg, 3)
    ref = data.ExactReference(base, "L2")
    truth = ref.topk(queries, 10)
    q_idx = np.random.default_rng(0).integers(0, 24, (6, 4))
    return cfg, ref, queries, truth, q_idx


def test_sound_answers_are_correct(world):
    cfg, ref, queries, truth, q_idx = world
    ids, scores = served(*world)
    checks, rec = check.compare(cfg, ref, queries, truth, q_idx, ids, scores)
    assert all(check.passed(c) for c in checks.values()), checks
    assert rec.mean() == 1.0 and checks["answers_compared"]["value"] == 24


def fault_rows_swapped(ids, scores):
    """Co-batched callers get each other's rows."""
    return ids[:, ::-1], scores[:, ::-1]


def fault_ids_altered(ids, scores):
    return (ids + 1) % 3000, scores


def fault_scores_altered(ids, scores):
    return ids, scores * (1 + 1e-4)


def fault_half_the_rows_left_out(ids, scores):
    ids, scores = ids.copy(), scores.copy()
    ids[:, 2:] = -1
    scores[:, 2:] = np.nan
    return ids, scores


def fault_no_answers(ids, scores):
    return ids[:0], scores[:0]


@pytest.mark.parametrize("fault,failing", [
    (fault_rows_swapped, "recall_at_10"),
    (fault_ids_altered, "recall_at_10"),
    (fault_scores_altered, "score_err"),
    (fault_half_the_rows_left_out, "short_rows"),
    (fault_no_answers, "answers_compared"),
])
def test_a_fault_where_answers_are_produced_is_not_correct(world, fault,
                                                           failing, capsys):
    cfg, ref, queries, truth, q_idx = world
    ids, scores = fault(*served(*world))
    checks, _ = check.compare(cfg, ref, queries, truth,
                              q_idx[:ids.shape[0]], ids, scores)
    assert not check.passed(checks[failing]), checks
    assert check.report(checks) is False
    assert "correct: false" in capsys.readouterr().err


def bf16(x):
    """Round float32 to bfloat16 (nearest even), kept in float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = ((u >> 16) & 1) + 0x7FFF
    return ((u + r) & 0xFFFF0000).astype(np.uint32).view(np.float32)


def test_lower_precision_control_is_not_correct(world):
    """The control at test size: the reference's own answers with the
    rerank's dot product computed as `high` computes it (each operand
    split into two bfloat16 pieces, the low x low product dropped). It
    keeps recall and fails `score_err`; on the chip the same control runs
    at the cell's own size through jax (benchmark/control.py, PERF.md)."""
    cfg, ref, queries, truth, q_idx = world
    ids = truth[q_idx]
    q = queries[np.repeat(q_idx.ravel(), 10)]
    v = ref.base[ids.ravel()]
    qh, vh = bf16(q), bf16(v)
    ql, vl = bf16(q - qh), bf16(v - vh)
    dots = ((qh * vh).astype(np.float64).sum(1) + (qh * vl).sum(1)
            + (ql * vh).sum(1))
    scores = ((q.astype(np.float64) ** 2).sum(1) - 2 * dots
              + (v.astype(np.float64) ** 2).sum(1)).reshape(ids.shape)
    checks, _ = check.compare(cfg, ref, queries, truth, q_idx, ids, scores)
    assert check.passed(checks["recall_at_10"])
    assert not check.passed(checks["score_err"]), checks["score_err"]
    # and the jax control's plumbing agrees with the reference at full
    # precision (the CPU backend computes float32 whatever is asked)
    c_ids, c_scores = control.answers(cfg, ref, queries, truth, "HIGHEST")
    checks, _ = check.compare(cfg, ref, queries, truth, q_idx, c_ids[q_idx],
                              c_scores[q_idx])
    assert all(check.passed(c) for c in checks.values()), checks


def test_nan_never_passes():
    assert not check.passed({"value": float("nan"), "limit": 1.0, "op": "<="})
