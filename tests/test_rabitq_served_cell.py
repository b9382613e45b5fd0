"""The deployment of benchmark cell `gist1m-rabitq.b64x4-closed`, small,
on the served path: VearchClient -> router -> PS -> engine ->
`IVFRaBitQIndex.search` -> `binary_refine_rerank`, at 8,000 x 960 with
64 lists and 64-row requests.

Held to the plain three-stage reference
(`benchmark/rabitq_reference.py`: numpy, float64, imports nothing of the
program) given the index's own trained centroids and assignments; to
the benchmark's exact reference (`benchmark/data.py` `ExactReference`)
under the configuration's own limits through `benchmark/check.py`
`compare`; and to what the path promises beside that: a funnel that
skips a stage, ignores `r0` or drops the mask is a different answer, a
deleted and a filtered row are never served, warmed shapes compile
nothing, the dispatch carries its launch stamp, and the `refine.place`
span and `/ps/stats` say what is placed.

960 is no multiple of 128, so the int8 rows and the raw store are placed
for a row gather as `[n / 2, 1920]` super-rows here as at a million rows
(`parallel/mesh.py` `row_pack`).
"""

import numpy as np
import pytest

from benchmark import cells, check, corpus, data, loadgen
from benchmark import rabitq_reference as ref
from vearch_tpu.ops import perf_model

CELL = "gist1m-rabitq.b64x4-closed"
ROWS, B, SEED = 8_000, 64, 3_141_592_653
#: depths at which every stage cuts inside a query's own cluster of
#: ~200 rows (at the cell's 512 / 256 a cluster fits inside r1)
TIGHT = {"r0": 64, "rerank": 32}
#: how near a cut a row the program lost may sit, as a share of
#: |q|^2 + |row|^2: the program rounds the query to bfloat16 for stages
#: 0 and 1 (2^-9 of an element; over 960 of them 1.8e-4 of that size at
#: the most, measured on these rows), and both the row and the cut's
#: row move
NEAR_TIE = 1e-3


class World:
    def __init__(self, tmp):
        from vearch_tpu.cluster.standalone import StandaloneCluster
        from vearch_tpu.sdk.client import VearchClient

        self.cfg = cells.Cell(CELL).config
        for f in self.cfg["space"]["fields"]:
            if f.get("index"):
                f["index"]["params"]["ncentroids"] = \
                    self.cfg["rehearsal"]["ncentroids"]
                # the cell's one chip: conftest.py gives pytest eight
                # CPU devices, and `auto` would serve from a mesh
                f["index"]["params"]["mesh_serving"] = "off"
        self.k = int(self.cfg["search"]["k"])
        self.params = self.cfg["search"]["index_params"]
        self.base, self.queries, _ = data.make_data(self.cfg, SEED % 2 ** 32,
                                                    ROWS)
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
        # the configuration's server settings, as a run applies them:
        # with shadow sampling on, the sampler's exact scans would place
        # the raw store a second time, as a matrix product wants it
        from vearch_tpu.cluster import rpc

        rpc.call(self.ps.addr, "POST", "/ps/engine/config",
                 {"partition_id": int(pid), "config": self.cfg["ps_config"]})
        self.engine.wait_for_index(timeout=600)
        self.index = self.engine.indexes["emb"]
        self.centroids = np.asarray(self.index.centroids, np.float64)
        self.lists = np.full(ROWS, -1, np.int64)
        for c, members in enumerate(self.index._members):
            self.lists[np.asarray(members, np.int64)] = c
        assert (self.lists >= 0).all()
        self.live = np.ones(ROWS, bool)

    def search(self, queries, filters=None, **index_params):
        out = self.client.search(
            corpus.DB, self.space,
            vectors=[{"field": "emb", "feature": queries}], limit=self.k,
            fields=[], profile=True, cache=False, filters=filters,
            index_params={**self.params, **index_params})
        (part,) = out["profile"]["partitions"].values()
        assert part["dispatches"]["tags"] == [
            self.cfg["serving"]["dispatch_tag"]] == ["binary_refine_rerank"]
        return out["documents"], out

    def served(self, queries, **kw):
        """(ids [n, k] as row numbers, scores [n, k]) of `queries`."""
        rec = loadgen.Recorder(queries.shape[0], self.k)
        docs, _ = self.search(queries, **kw)
        rec.add(0.0, 0.0, 0.0, np.arange(queries.shape[0]), docs, None, None)
        got = rec.arrays()
        return got["ids"][0], got["scores"][0]

    def funnel(self, queries, depths=None, **kw):
        d = {**self.params, **(depths or {})}
        return ref.funnel(self.base, self.centroids, self.lists, queries,
                          d.get("r0", self.cfg["serving"]["r0"]),
                          d["rerank"], self.k, **kw)

    def between(self) -> np.ndarray:
        """[B, d] queries between two stored rows of different clusters
        (not half way: the two would tie): a pool query sits on a stored
        row, and its own cluster is all any stage has to keep."""
        rng = np.random.default_rng(SEED)
        a, b = rng.integers(0, ROWS, 4 * B), rng.integers(0, ROWS, 4 * B)
        keep = np.flatnonzero(self.lists[a] != self.lists[b])[:B]
        return (0.45 * self.base[a[keep]] + 0.55 * self.base[b[keep]]
                ).astype(np.float32)

    def partition_stats(self) -> dict:
        from vearch_tpu.cluster import rpc

        (part,) = rpc.call(self.ps.addr, "GET",
                           "/ps/stats")["partitions"].values()
        return part

    def refine_stats(self) -> dict:
        return self.partition_stats()["refine"]["fields"]["emb"]

    def select_stats(self) -> dict:
        """{site tag: {select_width: dispatches}}, empty before the
        first full-scan dispatch."""
        return (self.partition_stats()["select"] or {"fields": {}}
                )["fields"].get("emb", {})


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    w = World(tmp_path_factory.mktemp("rabitq_cell"))
    yield w
    w.cluster.stop()


def rows_equal(w, queries, ids, want) -> np.ndarray:
    """[n] bool: the served row is the reference's, a swap of two rows
    whose exact distances tie in float32 aside."""
    same = (ids == want["ids"]).all(1)
    for i in np.flatnonzero(~same):
        if sorted(ids[i]) == sorted(want["ids"][i]):
            d = ref.sq_dists_to(queries[i:i + 1], w.base[ids[i]][None])[0]
            size = ((queries[i].astype(np.float64) ** 2).sum()
                    + (w.base[ids[i]].astype(np.float64) ** 2).sum(1))
            swapped = ids[i] != want["ids"][i]
            same[i] = np.ptp(d[swapped]) <= 3e-06 * size.max()
    return same


@pytest.mark.parametrize("queries,depths,least", [
    ("pool", None, 0.99), ("between", None, 0.99), ("between", TIGHT, 0.9)])
def test_served_topk_is_the_plain_funnels_and_its_scores_are_exact(
        world, queries, depths, least):
    """Every row scored by stage 0, exactly r0 rescored against the int8
    rows, exactly r1 reranked exactly, nothing dropped: at the cell's
    own depths (where 99 % of rows must be the reference's, id for id)
    and at depths where every stage cuts inside a cluster. Where a row
    differs, the row that one side kept and the other did not sits
    within the bfloat16 rounding of a stage's cut in the reference's
    own arithmetic."""
    w = world
    q = w.queries if queries == "pool" else w.between()
    depths = depths or {}
    ids, scores = w.served(q, **depths)
    assert (ids >= 0).all()
    want = w.funnel(q, depths)
    same = rows_equal(w, q, ids, want)
    assert same.mean() >= least, same.mean()
    d = {**w.params, **depths}
    r0, r1 = d.get("r0", w.cfg["serving"]["r0"]), d["rerank"]

    def near_a_cut(i, row) -> bool:
        m = ref.margins(w.base, w.centroids, w.lists, q[i], int(row), r0, r1)
        return min(abs(m["stage0"]), abs(m["stage1"])
                   if m["stage1"] is not None else np.inf) <= NEAR_TIE

    for i in np.flatnonzero(~same):
        differ = set(want["ids"][i]) ^ set(ids[i])
        # a row one side kept and the other did not sat at a cut, or is
        # the row that took (or lost) the place of one that did
        flipped = {row for row in differ if near_a_cut(i, row)}
        assert flipped and len(differ - flipped) <= len(flipped), (
            i, differ, flipped)
    # the final scores are the exact distances of the served ids:
    # float32 at `highest`, each term rounding at 2^-24 of itself and
    # cancelling down to the distance, a few 1e-7 of |q|^2 + |v|^2
    # (3.2e-7 in the CPU rehearsal at 960-d); the configuration's limit
    # leaves ten times of room under the nearest lower precision's 1e-5
    exact = ref.sq_dists_to(q, w.base[ids])
    size = ((q.astype(np.float64) ** 2).sum(1)[:, None]
            + (w.base[ids].astype(np.float64) ** 2).sum(2))
    assert (np.abs(scores - exact) / size).max() <= \
        w.cfg["limits"]["score_err_max"] == 3e-06


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
    assert checks["score_err"]["limit"] == 3e-06


@pytest.mark.parametrize("fault", ["skips_stage1", "ignores_r0"])
def test_a_funnel_that_lacks_a_stage_is_a_different_answer(world, fault):
    """The comparison has power: at depths that cut inside a cluster, a
    program that hands stage 0's first r1 straight to the exact stage,
    or that keeps r1 rows of stage 0 where it should keep r0, serves
    other rows than the plain funnel on a large share of queries; the
    served path does not."""
    w = world
    q = w.between()
    ids, _ = w.served(q, **TIGHT)
    sound = rows_equal(w, q, ids, w.funnel(q, TIGHT)).mean()
    if fault == "skips_stage1":
        broken = w.funnel(q, TIGHT, skip_stage1=True)
    else:
        broken = w.funnel(q, {**TIGHT, "r0": TIGHT["rerank"]})
    faulty = (ids == broken["ids"]).all(1).mean()
    assert sound >= 0.9 and faulty <= 0.5, (sound, faulty)
    # `lost_at` reads a miss: a neighbour the served answer holds and
    # the r0-less funnel lacks was dropped by THAT funnel's stage 0
    if fault == "ignores_r0":
        i = int(np.flatnonzero(~(ids == broken["ids"]).all(1))[0])
        row = int(next(iter(set(ids[i]) - set(broken["ids"][i]))))
        assert ref.lost_at(w.base, w.centroids, w.lists, q[i], row,
                           TIGHT["rerank"], TIGHT["rerank"]) == "stage0"
        assert ref.lost_at(w.base, w.centroids, w.lists, q[i], row,
                           TIGHT["r0"], TIGHT["rerank"]) == "kept"


def test_deleted_and_filtered_rows_are_never_served(world):
    """The mask reaches stage 0: a deleted row and a row the request's
    filter leaves out are in no answer, and the answer is the funnel's
    over the rows that are left; a funnel that drops the mask serves
    the deleted rows."""
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
    want = w.funnel(q, allowed=w.live)
    assert rows_equal(w, q, after, want).mean() >= 0.99
    unmasked = w.funnel(q)  # what a program without the mask serves
    assert np.isin(unmasked["ids"], gone_rows).any()
    flt = {"operator": "AND", "conditions": [
        {"field": "price", "operator": ">=", "value": 10.0},
        {"field": "price", "operator": "<", "value": 15.0}]}
    passing = w.live & (w.col >= 10.0) & (w.col < 15.0)
    filtered, _ = w.served(q, filters=flt)
    assert passing[filtered].all()
    assert rows_equal(w, q, filtered,
                      w.funnel(q, allowed=passing)).mean() >= 0.99


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


def test_the_dispatch_is_stamped_and_the_place_span_says_what_is_placed(
        world):
    """`kernel.binary_refine_rerank` carries `launch_us` (the jitted
    call returned before `device_get`), inside it nothing of
    `refine.place`, which ends at the launch and is tagged with the
    depths, the rows and the bytes of the planes and the int8 rows AS
    PLACED; `/ps/stats` `partitions.<pid>.refine` gives the same bytes
    beside the raw store's and the rows each stage scored."""
    from vearch_tpu.cluster import tracing

    w = world
    before = w.refine_stats()
    w.search(w.queries[:B])
    # the one that ended last: `snapshot()` walks the process's tracers
    # in no order, and under xdist an earlier file's may come after
    kernel = max((s for s in tracing.snapshot()
                  if s.name == "kernel.binary_refine_rerank"),
                 key=lambda s: s.t1_ns)
    (place,) = [s for s in tracing.snapshot() if s.name == "refine.place"
                and s.trace_id == kernel.trace_id]
    assert 0 < kernel.tags["launch_us"] * 1000 <= kernel.t1_ns - kernel.t0_ns
    assert kernel.tags["rows"] == B == kernel.tags["bucket_rows"]
    assert place.t1_ns <= kernel.t0_ns + 1_000_000  # ends at the launch
    index, store = w.index, w.index.store
    n_pad = index._bits._h8.shape[0]
    assert n_pad % 512 == 0 and n_pad >= ROWS
    planes = n_pad * 960 // 8 + 2 * 4 * n_pad
    mirror = n_pad * 960 + 2 * 4 * n_pad
    assert {k: v for k, v in place.tags.items() if k != "partition"} == {
        "r0": 512, "r1": 256, "rows": ROWS, "plane_bytes": planes,
        "mirror_bytes": mirror}
    # as placed for a gather: super-rows of two, and only that form
    assert index._mirror._dp8.shape == (n_pad // 2, 1920)
    assert index._mirror._d8 is None and store._device is None
    assert store._packed.shape == (store.capacity // 2, 1920)
    after = w.refine_stats()
    assert after["plane_bytes"] == planes
    assert after["mirror_bytes"] == mirror
    assert after["raw_bytes"] == store.capacity * (960 * 4 + 4)
    assert (after["r0"], after["r1"]) == (512, 256)
    assert after["searches"] == before["searches"] + 1
    assert {s: after["stage_rows"][s] - before["stage_rows"][s]
            for s in ("binary", "int8", "exact")} == {
        "binary": ROWS * B, "int8": 512 * B, "exact": 256 * B}


@pytest.mark.parametrize("tag,index_params", [
    ("binary_refine_rerank", {}),
    ("fused_scan_rerank", {"stage0": "off"})])
def test_a_full_scan_dispatch_says_how_wide_its_selection_sorts(
        world, tag, index_params):
    """`kernel.{tag}` of the three-stage dispatch and of the int8 chain
    (the same index with `stage0: off`) carries `select_width`, the
    scores a query that the widest sort of the program's `_select_topk`
    takes (`perf_model.select_width`; at these 8,192 padded rows the
    plain row, at the cell's 500,224 the 16 x r0 group maxima), and
    `/ps/stats` `partitions.<pid>.select` counts the dispatch under it."""
    from vearch_tpu.cluster import tracing

    w = world
    n_pad = w.index._bits._h8.shape[0]
    r = {"binary_refine_rerank": 512, "fused_scan_rerank": 256}[tag]
    width = perf_model.select_width(r, n_pad)
    assert width == n_pad == 8_192
    assert perf_model.select_width(512, 500_224) == 16 * 512
    before = w.select_stats().get(tag, {}).get(str(width), 0)
    out = w.client.search(
        corpus.DB, w.space,
        vectors=[{"field": "emb", "feature": w.queries[:B]}], limit=w.k,
        fields=[], profile=True, cache=False,
        index_params={**w.params, **index_params})
    (part,) = out["profile"]["partitions"].values()
    assert part["dispatches"]["tags"] == [tag]
    kernel = max((s for s in tracing.snapshot()
                  if s.name == f"kernel.{tag}"), key=lambda s: s.t1_ns)
    assert kernel.tags["select_width"] == width
    assert w.select_stats()[tag][str(width)] == before + 1
