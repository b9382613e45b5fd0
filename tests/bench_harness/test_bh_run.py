"""The rest of a run, without the look for a chip: a stand-in for the
served path answers the generator's own sender loop, the records go
through the harness's window view and comparison, and a fault planted
where an answer is produced has to come out as `correct` false."""

import threading

import numpy as np
import pytest

from benchmark import check, data, loadgen, run

from test_bh_data import small_cfg

ROWS, K = 4, 10
MIX_CLOSED = {"loop": "closed"}
MIX_OPEN = {"loop": "open"}


class FakeServedPath:
    """Answers like router -> PS -> engine would: per query row, k hits
    {"_id": "doc<row>", "_score": float32 squared distance}, nearest
    first. `fault` alters the answer where it is produced."""

    def __init__(self, ref, queries, truth, fault=None):
        self.ref, self.queries, self.truth, self.fault = (
            ref, queries, truth, fault)

    def search(self, q_idx):
        docs = []
        for qi in q_idx:
            ids = self.truth[qi]
            sc = self.ref.scores(self.queries, np.full(K, qi), ids)
            docs.append([{"_id": f"doc{i}", "_score": float(np.float32(s))}
                         for i, s in zip(ids, sc)])
        return self.fault(docs) if self.fault else docs


def drive(path, n_requests=12, clock_step=0.05, t0=100.0):
    """The generator's recorder fed by a closed loop on a made-up clock
    (no sleeps): request i is sent at t0 + i*step and answered one step
    later."""
    rec = loadgen.Recorder(ROWS, K)
    rng = np.random.default_rng(5)
    for i in range(n_requests):
        q_idx = rng.integers(0, path.queries.shape[0], ROWS)
        t_send = t0 + i * clock_step
        rec.add(t_send, t_send, t_send + clock_step, q_idx,
                path.search(q_idx), None, None)
    return rec


@pytest.fixture(scope="module")
def world():
    cfg = small_cfg()
    base, queries, _ = data.make_data(cfg, 9)
    ref = data.ExactReference(base, "L2")
    return cfg, ref, queries, ref.topk(queries, K)


def judged(world, fault, mix=MIX_CLOSED):
    cfg, ref, queries, truth = world
    rec = drive(FakeServedPath(ref, queries, truth, fault)).arrays()
    view, checks = run.judge(cfg, mix, rec, 100.0, 10.0, ref, queries, truth)
    return view, checks, check.report(checks)


@pytest.mark.parametrize("mix", [MIX_CLOSED, MIX_OPEN], ids=["closed", "open"])
def test_sound_path_is_correct_and_every_request_is_counted(world, mix):
    view, checks, correct = judged(world, None, mix)
    assert correct and view["attempted"] == 12 and view["failed"] == 0
    assert checks["answers_compared"]["value"] == 12 * ROWS
    np.testing.assert_allclose(view["lat_ms"], 50.0)


def altered_token(docs):
    docs[0][0]["_id"] = "doc2999"  # one hit of one row names another doc
    docs[0][0]["_score"] = 1e9
    return docs


def altered_score(docs):
    for row in docs:
        row[3]["_score"] *= 1.001
    return docs


def rows_of_another_caller(docs):
    return docs[::-1]


def half_the_batch_left_out(docs):
    return docs[:ROWS // 2]


def hits_left_out(docs):
    return [row[:K // 2] for row in docs]


def foreign_key(docs):
    docs[1][0]["_id"] = "bench_new"  # a document the corpus never held
    return docs


@pytest.mark.parametrize("fault,failing", [
    (altered_score, "score_err"),
    (rows_of_another_caller, "recall_at_10"),
    (half_the_batch_left_out, "short_rows"),
    (hits_left_out, "short_rows"),
    (foreign_key, "short_rows"),
])
def test_a_fault_in_the_served_path_is_not_correct(world, fault, failing):
    _, checks, correct = judged(world, fault)
    assert correct is False and not check.passed(checks[failing]), checks


def test_one_altered_answer_in_a_window_is_seen(world):
    """A single wrong hit among hundreds: recall stays above its floor,
    the score of the pair it names does not."""
    _, checks, correct = judged(world, altered_token)
    assert correct is False and not check.passed(checks["score_err"])


def test_a_request_that_failed_counts_as_failed_not_as_wrong(world):
    cfg, ref, queries, truth = world
    rec = drive(FakeServedPath(ref, queries, truth))
    rec.add(100.3, 100.3, 100.4, np.zeros(ROWS, int), None, None,
            "RpcError: 503")
    view, checks = run.judge(cfg, MIX_OPEN, rec.arrays(), 100.0, 10.0, ref,
                             queries, truth)
    assert view["attempted"] == 13 and view["failed"] == 1
    assert all(check.passed(c) for c in checks.values())


def test_recorder_is_safe_under_concurrent_senders(world):
    cfg, ref, queries, truth = world
    path = FakeServedPath(ref, queries, truth)
    rec = loadgen.Recorder(ROWS, K)

    def sender(tid):
        rng = np.random.default_rng(tid)
        for i in range(20):
            q = rng.integers(0, queries.shape[0], ROWS)
            rec.add(0.0, float(i), float(i) + 0.1, q, path.search(q), None,
                    None)

    threads = [threading.Thread(target=sender, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    r = rec.arrays()
    assert r["ok"].sum() == 160
    got = data.recall_rows(r["ids"].reshape(-1, K),
                           truth[r["q_idx"].ravel()])
    assert got.mean() == 1.0  # no row landed in another request's record


def test_profile_is_flattened_to_the_fields_the_readers_name():
    prof = {"merge_ms": 0.2, "partitions": {
        "1": {"rpc_ms": 30.0, "phases": {"total": 26.0, "queue": 1.5},
              "dispatches": {"tags": ["fused_scan_rerank"], "count": 1,
                             "per_dispatch_ms": {"fused_scan_rerank": 20.0}}},
        "2": {"rpc_ms": 10.0, "phases": {"total": 8.0}, "dispatches": {}}}}
    vals, tags = loadgen.parse_profile(prof)
    got = dict(zip(loadgen.PROFILE_FIELDS, vals))
    assert got == {"rpc_ms": 30.0, "merge_ms": 0.2, "ps_total_ms": 26.0,
                   "ps_queue_ms": 1.5, "ps_gate_wait_ms": 0.0,
                   "dispatch_sum_ms": 20.0, "dispatches": 1.0}
    assert tags == "fused_scan_rerank"
