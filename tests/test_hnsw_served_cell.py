"""The deployment of benchmark cell `gist1m-hnsw.b64x4-closed`, small,
on the served path: VearchClient -> router -> PS -> engine ->
`HNSWIndex.search` -> `int8_scan_rerank`, at 8,000 x 960, cosine, with
64-row requests.

Held to the plain reference of HNSW's device scan
(`benchmark/hnsw_reference.py`: numpy, float64, imports nothing of the
program); to the benchmark's exact reference (`benchmark/data.py`
`ExactReference`) under the configuration's own limits through
`benchmark/check.py` `compare`; and to what the path promises beside
that: a scan one candidate short, without its rerank or without the
normalisation is a different answer, deleted and filtered rows are never
served, warmed shapes compile nothing, a search is one stamped dispatch
under one `hnsw.place` span, and the depth is `efSearch`.

The stored rows are the cell's generator's, each scaled by a seeded
length in [0.5, 2): embeddings a user sends for a cosine space need not
be unit vectors, and a scan that skipped the normalisation would rank
by length. 960 is no multiple of 128, so the raw store is placed for a
row gather as `[n / 2, 1920]` super-rows here as at a million rows.
"""

import json
import os

import numpy as np
import pytest

from benchmark import cells, check, corpus, data, loadgen
from benchmark import hnsw_reference as ref
from vearch_tpu.ops import perf_model

CELL = "gist1m-hnsw.b64x4-closed"
ROWS, B, SEED = 8_000, 64, 2_718_281_828
#: a depth at which the int8 stage's cut falls inside a query's own
#: cluster of ~200 rows (the cell's 128 keeps most of it)
TIGHT = 16
#: the served score's tolerance against the exact cosine (float64) of
#: the served row: the configuration's limit. The program's float32
#: cosine at `highest` rounds each of 960 products at 2^-24 (3.3e-7 in
#: the CPU rehearsal); computed from bfloat16 rows and queries (2^-9 an
#: element) it is off by 1e-4 and more (asserted below)
SCORE_TOL = 3e-06
#: how near the int8 stage's cut a row the two sides split may sit, in
#: cosine: the program sums 960 products in float32 (a few 1e-6) and
#: quantises float32 rows, where an element within rounding of a half
#: level takes the other level (2.5e-5 of a score on these rows)
NEAR_CUT = 1e-4


class World:
    def __init__(self, tmp):
        from vearch_tpu.cluster import rpc
        from vearch_tpu.cluster.standalone import StandaloneCluster
        from vearch_tpu.sdk.client import VearchClient

        self.cfg = cells.Cell(CELL).config
        self.k = int(self.cfg["search"]["k"])
        self.params = self.cfg["search"]["index_params"]
        base, self.queries, _ = data.make_data(self.cfg, SEED % 2 ** 32,
                                               ROWS)
        lengths = np.random.default_rng(SEED).uniform(0.5, 2.0, ROWS)
        self.base = (base * lengths[:, None]).astype(np.float32)
        self.exact = data.ExactReference(self.base, self.cfg["metric"])
        self.truth = self.exact.topk(self.queries, self.k)
        self.cluster = StandaloneCluster(data_dir=str(tmp), n_ps=1).start()
        self.client = VearchClient(self.cluster.router_addr)
        self.client.create_database(corpus.DB)
        self.client.create_space(corpus.DB,
                                 corpus.space_config(self.cfg, ROWS))
        self.space = self.cfg["space"]["name"]
        self.col = data.scalar_column(self.cfg["scalar_columns"][0], ROWS)
        for lo in range(0, ROWS, 2000):
            self.client.upsert(corpus.DB, self.space, [
                {"_id": f"doc{i}", "emb": self.base[i],
                 "price": float(self.col[i])}
                for i in range(lo, lo + 2000)])
        self.ps = self.cluster.ps_nodes[0]
        (pid, self.engine), = self.ps.engines.items()
        rpc.call(self.ps.addr, "POST", "/ps/engine/config",
                 {"partition_id": int(pid), "config": self.cfg["ps_config"]})
        self.engine.wait_for_index(timeout=600)
        self.index = self.engine.indexes["emb"]
        self.live = np.ones(ROWS, bool)

    def search(self, queries, filters=None, limit=None, **index_params):
        out = self.client.search(
            corpus.DB, self.space,
            vectors=[{"field": "emb", "feature": queries}],
            limit=limit or self.k, fields=[], profile=True, cache=False,
            filters=filters, index_params={**self.params, **index_params})
        (part,) = out["profile"]["partitions"].values()
        assert part["dispatches"]["tags"] == [
            self.cfg["serving"]["dispatch_tag"]] == \
            perf_model.DOCUMENTED_DISPATCHES["hnsw_scan_fused"]
        return out["documents"], out

    def served(self, queries, limit=None, **kw):
        """(ids [n, k] as row numbers, scores [n, k]) of `queries`."""
        k = limit or self.k
        rec = loadgen.Recorder(queries.shape[0], k)
        docs, _ = self.search(queries, limit=limit, **kw)
        rec.add(0.0, 0.0, 0.0, np.arange(queries.shape[0]), docs, None, None)
        got = rec.arrays()
        return got["ids"][0], got["scores"][0]

    def reference(self, queries, ef=None, k=None, **kw):
        return ref.search(self.base, queries, ef or self.params["efSearch"],
                          k or self.k, self.cfg["metric"], **kw)

    def between(self) -> np.ndarray:
        """[B, d] queries between two stored rows (not half way: the two
        would tie): a query with no stored row of its own."""
        rng = np.random.default_rng(SEED)
        a, b = rng.integers(0, ROWS, B), rng.integers(0, ROWS, B)
        return (0.45 * ref.normalise(self.base[a])
                + 0.55 * ref.normalise(self.base[b])).astype(np.float32)


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    w = World(tmp_path_factory.mktemp("hnsw_cell"))
    yield w
    w.cluster.stop()


def rows_equal(w, queries, ids, want) -> np.ndarray:
    """[n] bool: the served row is the reference's, a swap of two rows
    whose exact cosines tie in float32 aside."""
    same = (ids == want["ids"]).all(1)
    for i in np.flatnonzero(~same):
        if sorted(ids[i]) == sorted(want["ids"][i]):
            s = ref.exact_scores(w.base, queries[i:i + 1], ids[i][None],
                                 "Cosine")[0]
            swapped = ids[i] != want["ids"][i]
            same[i] = np.ptp(s[swapped]) <= SCORE_TOL
    return same


@pytest.mark.parametrize("queries,ef,least", [
    ("pool", None, 0.99), ("between", None, 0.99), ("between", TIGHT, 0.95)])
def test_served_topk_is_the_references_and_its_scores_are_exact(
        world, queries, ef, least):
    """Every row scored from its int8 form, exactly efSearch of them
    reranked by their exact cosine, nothing dropped: at the cell's depth
    and at one that cuts inside a cluster. Where a row differs, the row
    one side kept sat at the int8 stage's cut in the reference's own
    scores (the program accumulates in float32, the reference in
    float64). The served scores are the exact cosines of the served
    rows; from bfloat16 inputs they would not be."""
    w = world
    q = w.queries if queries == "pool" else w.between()
    extra = {} if ef is None else {"efSearch": ef}
    ids, scores = w.served(q, **extra)
    assert (ids >= 0).all()
    want = w.reference(q, ef=ef)
    same = rows_equal(w, q, ids, want)
    assert same.mean() >= least, same.mean()
    r = ef or w.params["efSearch"]
    for i in np.flatnonzero(~same):
        s = ref.scan_scores(w.base, q[i:i + 1], "Cosine")[0]
        cut = np.sort(s)[::-1][r - 1]
        differ = set(ids[i]) ^ set(want["ids"][i])
        # a row one side kept and the other did not sat at the cut, or
        # is the row that took (or lost) the place of one that did
        flipped = {row for row in differ if abs(s[row] - cut) <= NEAR_CUT}
        assert flipped and len(differ - flipped) <= len(flipped), (
            i, differ, flipped)
    exact = ref.exact_scores(w.base, q, ids, "Cosine")
    assert np.abs(scores - exact).max() <= \
        w.cfg["limits"]["score_err_max"] == SCORE_TOL
    rounded = ref.exact_scores(ref.bf16(w.base), ref.bf16(q), ids, "Cosine")
    assert np.abs(rounded - exact).max() > 30 * SCORE_TOL


def test_served_cell_passes_its_own_comparison(world):
    """Every pool query through the served path at the cell's own
    parameters, judged as a run is."""
    w = world
    rec = loadgen.Recorder(B, w.k)
    for lo in range(0, w.queries.shape[0], B):
        q_idx = np.arange(lo, lo + B)
        docs, _ = w.search(w.queries[q_idx])
        rec.add(0.0, 0.0, 0.0, q_idx, docs, None, None)
    got = rec.arrays()
    checks, _ = check.compare(w.cfg, w.exact, w.queries, w.truth,
                              got["q_idx"], got["ids"], got["scores"])
    assert all(check.passed(c) for c in checks.values()), checks
    assert checks["answers_compared"]["value"] == w.queries.shape[0]
    assert checks["recall_at_10"]["limit"] == 0.95
    assert checks["score_err"]["limit"] == SCORE_TOL


@pytest.mark.parametrize("fault", ["one_short", "no_rerank", "unnormalised"])
def test_a_scan_that_lacks_a_step_is_a_different_answer(world, fault):
    """The comparison has power. At efSearch = k = 16 every candidate
    is served, so a scan one candidate short leaves a row out of every
    answer; a scan whose int8 scores are served without the rerank
    misses the exact cosine by far more than the tolerance; a scan of
    the rows as stored (no normalisation) ranks by length and, at a
    depth that cuts inside a cluster, keeps other rows. The served path
    is none of these."""
    w = world
    q = w.between()
    if fault == "one_short":
        ids, _ = w.served(q, limit=TIGHT, efSearch=TIGHT)
        sound = w.reference(q, ef=TIGHT, k=TIGHT)
        broken = w.reference(q, ef=TIGHT - 1, k=TIGHT)
        assert rows_equal(w, q, ids, sound).mean() >= 0.95
        assert (broken["ids"] < 0).any(1).all() and (ids >= 0).all()
        assert ((ids == broken["ids"]).all(1)).mean() == 0.0
    elif fault == "no_rerank":
        ids, scores = w.served(q)
        broken = w.reference(q, rerank=False)
        exact = ref.exact_scores(w.base, q, broken["ids"], "Cosine")
        assert (np.abs(broken["scores"] - exact).max(1) > SCORE_TOL).all()
        assert np.abs(scores - ref.exact_scores(w.base, q, ids, "Cosine")
                      ).max() <= SCORE_TOL
    else:
        ids, _ = w.served(q, efSearch=TIGHT)
        broken = w.reference(q, ef=TIGHT, normalised=False)
        sound = rows_equal(w, q, ids, w.reference(q, ef=TIGHT)).mean()
        faulty = (ids == broken["ids"]).all(1).mean()
        assert sound >= 0.95 and faulty <= 0.5, (sound, faulty)


def test_deleted_and_filtered_rows_are_never_served(world):
    """The mask reaches the int8 stage: a deleted row and a row the
    request's filter leaves out are in no answer, and the answer is the
    reference's over the rows that are left; a scan without the mask
    serves the deleted rows."""
    w = world
    q = w.queries[:B]
    ids, _ = w.served(q)
    gone_rows = np.array(sorted({int(r[0]) for r in ids})[:20])
    assert w.client.delete(
        corpus.DB, w.space,
        document_ids=[f"doc{i}" for i in gone_rows]) == gone_rows.size
    w.live[gone_rows] = False
    after, _ = w.served(q)
    assert not np.isin(after, gone_rows).any()
    assert rows_equal(w, q, after,
                      w.reference(q, allowed=w.live)).mean() >= 0.99
    assert np.isin(w.reference(q)["ids"], gone_rows).any()
    flt = {"operator": "AND", "conditions": [
        {"field": "price", "operator": ">=", "value": 10.0},
        {"field": "price", "operator": "<", "value": 15.0}]}
    passing = w.live & (w.col >= 10.0) & (w.col < 15.0)
    filtered, _ = w.served(q, filters=flt)
    assert passing[filtered].all()
    assert rows_equal(w, q, filtered,
                      w.reference(q, allowed=passing)).mean() >= 0.99


def test_warmed_same_shape_searches_compile_nothing(world):
    """Every row count one dispatch of the mix can hold, twice; then
    again: `compiled_program_counts()` does not move (the cell's
    `window_compiles` 0)."""
    w = world
    mix = cells.Cell(CELL).traffic
    assert mix["warm_rows"] == [64, 128, 256]
    for rows in mix["warm_rows"]:
        for _ in range(2):
            w.search(np.resize(w.queries, (rows, w.queries.shape[1])))
    before = perf_model.compiled_program_counts()
    for rows in mix["warm_rows"]:
        w.search(np.resize(w.queries[::-1], (rows, w.queries.shape[1])))
    assert perf_model.compiled_program_counts() == before


def _spans_of_last(name):
    """The spans of the trace whose `name` span ended last (under xdist
    an earlier file's tracers may come after in `snapshot()`)."""
    from vearch_tpu.cluster import tracing

    last = max((s for s in tracing.snapshot() if s.name == name),
               key=lambda s: s.t1_ns)
    return last, [s for s in tracing.snapshot()
                  if s.trace_id == last.trace_id]


def test_one_stamped_dispatch_a_search_under_one_place_span(world):
    """A search is ONE `fused_scan_rerank` dispatch whose
    `kernel.fused_scan_rerank` carries `launch_us` (the jitted call
    returned before `device_get`) and `select_width`, with ONE
    `hnsw.place` before it on the same thread, ending at the launch and
    tagged with the depth, the rows, the int8 rows' bytes as placed and
    the raw store's super-row width; the store is placed once, as
    super-rows of two."""
    w = world
    w.search(w.queries[:B])
    kernel, mine = _spans_of_last("kernel.fused_scan_rerank")
    assert [s.name for s in mine].count("kernel.fused_scan_rerank") == 1
    (place,) = [s for s in mine if s.name == "hnsw.place"]
    assert 0 < kernel.tags["launch_us"] * 1000 <= kernel.t1_ns - kernel.t0_ns
    assert kernel.tags["rows"] == B == kernel.tags["bucket_rows"]
    index, store = w.index, w.index.store
    n_pad = index._mirror._h8.shape[0]
    assert n_pad % 512 == 0 and n_pad >= ROWS
    assert kernel.tags["select_width"] == perf_model.select_width(128, n_pad)
    assert place.t1_ns <= kernel.t0_ns + 1_000_000  # ends at the launch
    assert place.tid == kernel.tid and 0 <= place.cpu_ns
    assert {k: v for k, v in place.tags.items() if k != "partition"} == {
        "ef": 128, "rows": ROWS, "mirror_bytes": n_pad * 960 + 2 * 4 * n_pad,
        "store_pack": 2}
    assert store._packed.shape == (-(-store.capacity // 2), 1920)
    assert store._device is None and index._mirror._dp8 is None


@pytest.mark.parametrize("index_params,depth", [
    ({"rerank": 16}, 128), ({"rerank": 1024}, 128),
    ({"efSearch": 64, "rerank": 1024}, 64), ({"efSearch": 4}, 16)])
def test_the_sites_depth_is_efsearch_whatever_rerank_says(
        world, index_params, depth):
    """HNSW reranks max(efSearch, the engine's fetch-k tier) candidates
    (16 for k = 10) and never reads `rerank`, which the configuration
    carries for the benchmark's roofline reader only."""
    w = world
    w.search(w.queries[:B], **index_params)
    _, mine = _spans_of_last("hnsw.place")
    (place,) = [s for s in mine if s.name == "hnsw.place"]
    assert place.tags["ef"] == depth


def test_configuration_file_is_held_to_the_products_rules():
    """`benchmark/configs/gist1m-960-hnsw.json` states the deployment
    the product serves: BASELINE.json row 4's widths, the index the
    product builds from its params, the serving path `graph: auto`
    resolves to for a RAM store, the program the dispatch tag names."""
    from vearch_tpu.engine.raw_vector import RawVectorStore
    from vearch_tpu.engine.types import IndexParams, MetricType
    from vearch_tpu.index.hnsw import HNSWIndex
    from vearch_tpu.index.registry import create_index

    cell = cells.Cell(CELL)
    cfg = cell.config
    assert (cfg["rows"], cfg["dimension"], cfg["metric"]) == (
        1_000_000, 960, "Cosine")
    assert cfg["reduced"] == [] and cfg["chips"] == cell.chips == 1
    (field,) = [f for f in cfg["space"]["fields"] if f.get("index")]
    assert field["dimension"] == cfg["dimension"]
    spec = field["index"]
    assert (spec["index_type"], spec["metric_type"]) == ("HNSW", "Cosine")
    s = cfg["search"]
    assert s["k"] == 10 and s["index_params"]["efSearch"] == 128
    assert s["index_params"]["rerank"] == s["index_params"]["efSearch"]
    assert (cfg["space"]["partition_num"], cfg["space"]["replica_num"]) == (
        1, 1)
    index = create_index(
        IndexParams("HNSW", MetricType(spec["metric_type"]),
                    spec["params"]), RawVectorStore(cfg["dimension"]))
    assert isinstance(index, HNSWIndex) and not index.use_graph
    # efSearch rides every request (the index's own default is 64)
    assert (index.m, index.ef_construction) == (32, 40)
    assert index.metric is MetricType.COSINE
    serving = cfg["serving"]
    assert [serving["dispatch_tag"]] == \
        perf_model.DOCUMENTED_DISPATCHES["hnsw_scan_fused"]
    assert serving["program"] == serving["kernel"] == "int8_scan_rerank"
    assert cfg["limits"] == {"recall_at_10_min": 0.95,
                             "score_err_max": SCORE_TOL}
    assert cfg["ps_config"]["quality"]["sample_rate"] == 0.0
    with open(os.path.join(cells.REPO, "BASELINE.json")) as f:
        row = json.load(f)["configs"][3]
    assert row.startswith("HNSW, GIST1M d=960, cosine, ef_search=128")
    (entry,) = [c for c in cells.benchmark_json()["configs"]
                if c["name"] == cfg["name"]]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
