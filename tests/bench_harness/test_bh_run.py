"""The rest of a run, without the look for a chip: a stand-in for the
served path answers the generator's own sender loop, the records go
through the harness's window view and comparison, and a fault planted
where an answer is produced has to come out as `correct` false."""

import json
import os
import threading
import time

import numpy as np
import pytest

from benchmark import cells, check, data, loadgen, run

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


# -- under a filter ---------------------------------------------------------------

FILTER = {"column": "price", "modulo": 50, "classes": [
    {"name": "wide", "weight": 0.5, "width": 30},
    {"name": "narrow", "weight": 0.5, "width": 1}]}


class FilteredServedPath:
    """Answers each request under its own `lo <= price < lo + width`, as
    the engine's masked scan would; `fault(q_idx, lo, width)` changes
    what the path believes it was asked."""

    def __init__(self, ref, queries, col, fault=None):
        self.ref, self.queries, self.col, self.fault = ref, queries, col, fault
        self.score = ref.scores

    def search(self, q_idx, lo, width):
        if self.fault:
            lo, width = self.fault(lo, width)
        allowed = (self.col >= lo) & (self.col < lo + width)
        docs = []
        for qi in q_idx:
            ids = self.ref.topk(self.queries[qi:qi + 1], K, allowed)[0]
            sc = self.score(self.queries, np.full(K, qi), ids)
            docs.append([{"_id": f"doc{i}", "_score": float(np.float32(s))}
                         for i, s in zip(ids, sc)])
        return docs


@pytest.fixture(scope="module")
def filtered_world(world):
    cfg, ref, queries, truth = world
    col = data.scalar_column(cfg["scalar_columns"][0], ref.base.shape[0])
    return cfg, ref, queries, truth, col


def judged_filtered(filtered_world, fault=None, against_unfiltered=False,
                    tmp_path=None, score=None):
    cfg, ref, queries, truth, col = filtered_world
    path = FilteredServedPath(ref, queries, col, fault)
    if score:
        path.score = score
    rec = loadgen.Recorder(ROWS, K)
    rng = np.random.default_rng(5)
    for i in range(12):
        q_idx = rng.integers(0, queries.shape[0], ROWS)
        lo, width = loadgen.draw_filter(rng, FILTER)
        t = 100.0 + i * 0.05
        rec.add(t, t, t + 0.05, q_idx, path.search(q_idx, lo, width), None,
                None, lo, width)
    filtered = None if against_unfiltered else run.FilteredTruth(
        ref, queries, K, col,
        str(tmp_path / "truth-filter-price.npz") if tmp_path else None)
    view, checks = run.judge(cfg, MIX_CLOSED, rec.arrays(), 100.0, 10.0, ref,
                             queries, truth, filtered)
    return view, checks, check.report(checks), filtered


def test_sound_filtered_path_is_correct_and_its_truth_is_kept_apart(
        filtered_world, tmp_path):
    view, checks, correct, filtered = judged_filtered(filtered_world,
                                                      tmp_path=tmp_path)
    assert correct and view["failed"] == 0
    assert checks["filter_violations"] == {"value": 0, "limit": 0, "op": "<="}
    assert checks["recall_at_10"]["value"] == 1.0
    win = view["win"]
    assert np.isfinite(win["f_lo"]).all() and set(win["f_width"]) <= {1., 30.}
    share = filtered.pass_share(win["f_lo"], win["f_width"],
                                FILTER["classes"])
    assert {k: v["pass_share"] for k, v in share.items()} == {
        "narrow": pytest.approx(0.02), "wide": pytest.approx(0.6)}
    assert sum(v["requests"] for v in share.values()) == 12
    # the cache is a file of its own, read back by the next run of the seed
    assert os.listdir(tmp_path) == ["truth-filter-price.npz"]
    again = run.FilteredTruth(filtered.ref, filtered.queries, K, filtered.col,
                              str(tmp_path / "truth-filter-price.npz"))
    np.testing.assert_array_equal(again.truth, filtered.truth)
    again.ref = None  # nothing left to compute: every set is in the file
    again.of(win["f_lo"], win["f_width"])


def ignores_the_filter(lo, width):
    return 0.0, 50.0


def another_requests_bounds(lo, width):
    """A wide request answered under a narrower range inside its own:
    every hit passes the request's filter, and is still not its answer."""
    return (lo + 10.0, 5.0) if width > 5 else (lo, width)


@pytest.mark.parametrize("fault,failing,passing", [
    (ignores_the_filter, "filter_violations", "score_err"),
    (another_requests_bounds, "recall_at_10", "filter_violations"),
])
def test_a_fault_under_a_filter_is_not_correct(filtered_world, fault,
                                               failing, passing):
    _, checks, correct, _ = judged_filtered(filtered_world, fault)
    assert correct is False and not check.passed(checks[failing]), checks
    assert check.passed(checks[passing]), checks


def test_lower_precision_control_under_a_filter_is_not_correct(
        filtered_world):
    """The control at test size, in the filtered cell: the reference's own
    filtered answers with the rerank's product as `high` computes it keep
    recall and the filter, and fail `score_err` (test_bh_data.py has the
    arithmetic; on the chip `--control` runs it at the cell's size)."""
    from test_bh_data import bf16

    _, ref, _, _, _ = filtered_world

    def scores_at_high(queries, q_idx, ids):
        q, v = queries[q_idx], ref.base[ids]
        qh, vh = bf16(q), bf16(v)
        ql, vl = bf16(q - qh), bf16(v - vh)
        dots = ((qh * vh).astype(np.float64).sum(1) + (qh * vl).sum(1)
                + (ql * vh).sum(1))
        return ((q.astype(np.float64) ** 2).sum(1) - 2 * dots
                + (v.astype(np.float64) ** 2).sum(1))

    _, checks, correct, _ = judged_filtered(filtered_world,
                                            score=scores_at_high)
    assert correct is False and not check.passed(checks["score_err"])
    assert check.passed(checks["recall_at_10"])
    assert check.passed(checks["filter_violations"])


def test_filtered_answers_held_to_the_unfiltered_truth_are_not_correct(
        filtered_world):
    """The fault this PR's harness could have itself: a sound filtered
    path judged against `truth.npy`."""
    _, checks, correct, _ = judged_filtered(filtered_world,
                                            against_unfiltered=True)
    assert correct is False and not check.passed(checks["recall_at_10"])
    assert "filter_violations" not in checks


def test_a_drawn_filter_passes_width_over_modulo_of_the_rows():
    rng = np.random.default_rng(2_987_654_321)
    col = data.scalar_column({"modulo": 50}, 20_000)
    widths = []
    for _ in range(1000):
        lo, width = loadgen.draw_filter(rng, FILTER)
        assert 0.0 <= lo and lo + width <= 50.0
        f32 = (col.astype(np.float32) >= np.float32(lo)) & (
            col.astype(np.float32) < np.float32(lo + width))
        f64 = (col >= lo) & (col < lo + width)
        assert f64.mean() == width / 50 and (f32 == f64).all()
        widths.append(width)
    assert 400 < widths.count(30.0) < 600 and set(widths) == {1.0, 30.0}
    with pytest.raises(ValueError):  # every row passes: nothing to draw
        loadgen.draw_filter(rng, {"modulo": 50, "classes": [
            {"name": "all", "weight": 1.0, "width": 50}]})
    body = loadgen.filter_body("price", 3.5, 30.0)
    assert body == {"operator": "AND", "conditions": [
        {"field": "price", "operator": ">=", "value": 3.5},
        {"field": "price", "operator": "<", "value": 33.5}]}


# -- a mix without `filter` is what it was ------------------------------------------

#: the spec the parent of the PR that added `filter` wrote for worker 1 of
#: a closed and of an open mix (benchmark/run.py spawn_generators, PR 30)
def parent_spec(cfg, mix, w, seed=7):
    spec = {
        "router": "r:1", "db": "bench", "space": cfg["space"]["name"],
        "field": cfg["vector_field"], "k": cfg["search"]["k"],
        "index_params": cfg["search"]["index_params"],
        "cache": bool(mix["cache"]), "profile": False,
        "loop": mix["loop"], "rows": int(mix["rows_per_request"]),
        "threads": int(mix["threads"]), "seed": seed, "worker": w,
        "pool_path": "/p/pool.npy", "t_start": 10.0, "t_stop": 33.25,
        "drain_s": 60.0, "out": f"/p/gen{w}.npz"}
    if mix["loop"] == "open":
        spec["due_path"] = f"/p/due{w}.npy"
    return spec


@pytest.mark.parametrize("cell", ["sift1m.b64x4-closed", "sift1m.b1-open",
                                  "cohere1m.b64x4-closed",
                                  "deep10m-mesh4.b64x4-closed"])
def test_a_mix_without_filter_gets_the_specs_it_got_before(cell):
    c = cells.Cell(cell)
    specs = run.generator_specs(c.traffic, c.config, "r:1", "/p/pool.npy",
                                "/p", 7, 10.0, 33.25, False)
    want = [parent_spec(c.config, c.traffic, w)
            for w in range(int(c.traffic["processes"]))]
    assert json.dumps(specs) == json.dumps(want)  # keys, order and values


def test_the_filter_mix_tells_the_generator_its_column_and_classes():
    c = cells.Cell("sift1m.b64x4-filter")
    specs = run.generator_specs(c.traffic, c.config, "r:1", "/p/pool.npy",
                                "/p", 7, 10.0, 33.25, False)
    assert len(specs) == 4 and all(
        s["filter"]["column"] == "price" and s["filter"]["modulo"] == 50
        and [(k["width"], k["weight"]) for k in s["filter"]["classes"]]
        == [(30, 0.5), (1, 0.5)] for s in specs)
    with pytest.raises(KeyError):
        run.mix_filter({**c.config, "scalar_columns": []}, c.traffic)


def sent_by(monkeypatch, loop, spec_extra, n):
    """The first n (q_idx, filter) a generator loop hands its sender."""
    sent = []

    def fake_sender(spec, pool, rec):
        def send(q_idx, t_due, flt=None):
            sent.append((np.array(q_idx), flt))
            if len(sent) >= n:
                spec["t_stop"] = 0.0  # a closed loop ends at its next look
        return send

    monkeypatch.setattr(loadgen, "make_sender", fake_sender)
    pool = np.zeros((512, 4), np.float32)
    now = time.monotonic()
    spec = {"seed": 11, "worker": 2, "rows": 3, "threads": 1,
            "t_start": now, "t_stop": now + 30.0, "drain_s": 5.0,
            **spec_extra}
    loop(spec, pool, None)
    return sent[:n]


def test_query_picks_of_a_closed_mix_without_filter_are_the_parents(
        monkeypatch):
    sent = sent_by(monkeypatch, loadgen.run_closed, {}, 20)
    rng = np.random.default_rng([11, 2, 0])  # the parent's stream and draws
    for q_idx, flt in sent:
        np.testing.assert_array_equal(q_idx, rng.integers(0, 512, 3))
        assert flt is None
    filtered = sent_by(monkeypatch, loadgen.run_closed, {"filter": FILTER}, 20)
    assert all(f is not None and f[1] in (1.0, 30.0) for _, f in filtered)
    assert len({f for _, f in filtered}) == 20  # no two filters alike


def test_query_picks_of_an_open_mix_without_filter_are_the_parents(
        monkeypatch, tmp_path):
    due = str(tmp_path / "due.npy")
    np.save(due, time.monotonic() + np.zeros(16))
    sent = sent_by(monkeypatch, loadgen.run_open, {"due_path": due}, 16)
    want = np.random.default_rng([11, 2]).integers(0, 512, (16, 3))
    np.testing.assert_array_equal(np.stack([q for q, _ in sent]), want)
    assert all(f is None for _, f in sent)
    filtered = sent_by(monkeypatch, loadgen.run_open,
                       {"due_path": due, "filter": FILTER}, 16)
    np.testing.assert_array_equal(np.stack([q for q, _ in filtered]), want)
    assert len({f for _, f in filtered}) == 16
