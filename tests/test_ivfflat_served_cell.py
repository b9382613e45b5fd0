"""The deployment of benchmark cell `sift1m-ivfflat.b64x4-closed`, small,
on the served path: VearchClient -> router -> PS -> engine ->
`IVFFlatIndex.search` -> `ivfflat_candidates`, at 20,000 x 128 with
64 lists and 64-row requests.

Held to the plain IVF-Flat reference (`benchmark/ivfflat_reference.py`:
numpy, float64, imports nothing of the program) given the index's own
trained centroids, id for id; to the benchmark's exact reference
(`benchmark/data.py` `ExactReference`) under the configuration's own
limits through `benchmark/check.py` `compare`; and to what the probe
regime promises beside that: a deleted and a filtered row are never
returned, a padded probe slot duplicates nothing, an appended row is
found after the re-publish it forces.

The scan runs in tiles here as it does at a million rows: the module's
world lowers `ops/ivf.py` `PROBE_SLICE_BYTES` to 128 rows' worth, so a
list of `cap` slots is `cap / 128` scan steps.
"""

import numpy as np
import pytest

from benchmark import cells, check, corpus, data, ivfflat_reference, loadgen
from vearch_tpu.ops import ivf as ivf_ops
from vearch_tpu.ops import perf_model

CELL = "sift1m-ivfflat.b64x4-closed"
ROWS, B, SEED = 20_000, 64, 3_141_592_653
TILE_ROWS = 128


class World:
    def __init__(self, tmp):
        from vearch_tpu.cluster.standalone import StandaloneCluster
        from vearch_tpu.sdk.client import VearchClient

        self.cfg = cells.Cell(CELL).config
        for f in self.cfg["space"]["fields"]:
            if f.get("index"):
                f["index"]["params"]["ncentroids"] = \
                    self.cfg["rehearsal"]["ncentroids"]
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
        for lo in range(0, ROWS, 5000):
            self.client.upsert(corpus.DB, self.space, [
                {"_id": f"doc{i}", "emb": self.base[i],
                 "price": float(self.col[i])}
                for i in range(lo, lo + 5000)])
        self.ps = self.cluster.ps_nodes[0]
        self.engine = next(iter(self.ps.engines.values()))
        self.engine.wait_for_index(timeout=600)
        self.index = self.engine.indexes["emb"]
        self.centroids = np.asarray(self.index.centroids, np.float64)
        self.lists = self.reference_lists()

    def reference_lists(self) -> np.ndarray:
        """The reference's assignment of every row, ties aside. The
        program assigns with `ops/kmeans.py` `assign_clusters`, whose
        product takes rows and centroids rounded to bfloat16 (`_IVFBase`'s
        choice, shared with the three IVFPQ configurations): a row
        whose two nearest centroids are equally near to within that
        rounding (2^-9 of an element; 1e-3 of |x|^2 + |c|^2 over 128 of
        them) may go to either, and goes where the program put it: one
        row of these 20,000 (gap 1.0e-4). Everywhere else the program
        must have put the row where the reference does."""
        lists = ivfflat_reference.assign(self.base, self.centroids)
        placed = np.full(ROWS, -1, np.int64)
        for c, members in enumerate(self.index._members):
            placed[np.asarray(members, np.int64)] = c
        differ = np.flatnonzero(placed != lists)
        d = ivfflat_reference.sq_dists(self.base[differ], self.centroids)
        size = ((self.base[differ].astype(np.float64) ** 2).sum(1)
                + (self.centroids[lists[differ]] ** 2).sum(1))
        gap = d[np.arange(differ.size), placed[differ]] - d.min(1)
        assert (gap <= 1e-3 * size).all(), (differ, gap / size)
        assert differ.size <= ROWS // 1000
        lists[differ] = placed[differ]
        return lists

    def search(self, queries, filters=None, **index_params):
        out = self.client.search(
            corpus.DB, self.space,
            vectors=[{"field": "emb", "feature": queries}], limit=self.k,
            fields=[], profile=True, cache=False, filters=filters,
            index_params={**self.params, **index_params})
        (part,) = out["profile"]["partitions"].values()
        assert part["dispatches"]["tags"] == [
            self.cfg["serving"]["dispatch_tag"]] == ["ivfflat_scan"]
        return out["documents"], part

    def served(self, q_idx, **kw):
        """(ids [n, k] as row numbers, scores [n, k]) of pool queries."""
        rec = loadgen.Recorder(q_idx.size, self.k)
        docs, _ = self.search(self.queries[q_idx], **kw)
        rec.add(0.0, 0.0, 0.0, q_idx, docs, None, None)
        got = rec.arrays()
        return got["ids"][0], got["scores"][0]

    def ivf_stats(self) -> dict:
        from vearch_tpu.cluster import rpc

        (part,) = rpc.call(self.ps.addr, "GET",
                           "/ps/stats")["partitions"].values()
        return part["ivf"]["fields"]["emb"]


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    limit = ivf_ops.PROBE_SLICE_BYTES
    ivf_ops.PROBE_SLICE_BYTES = TILE_ROWS * 128 * 4
    w = World(tmp_path_factory.mktemp("ivfflat_cell"))
    yield w
    w.cluster.stop()
    ivf_ops.PROBE_SLICE_BYTES = limit


def between_lists(w) -> np.ndarray:
    """[B, d] queries between two stored rows of different lists (not
    half way: the two would tie): their neighbours straddle lists, so
    how many lists are probed decides the answer (the pool's queries
    sit beside a stored row, deep inside one tight cluster, and read
    the same at any nprobe)."""
    rng = np.random.default_rng(SEED)
    a = rng.integers(0, ROWS, 4 * B)
    b = rng.integers(0, ROWS, 4 * B)
    keep = np.flatnonzero(w.lists[a] != w.lists[b])[:B]
    return (0.45 * w.base[a[keep]] + 0.55 * w.base[b[keep]]).astype(
        np.float32)


@pytest.mark.parametrize("queries,nprobe", [("pool", 32), ("between", 32),
                                            ("between", 1)])
def test_served_ids_are_the_plain_ivf_flat_references(world, queries, nprobe):
    """The same lists probed, every probed row scored, nothing dropped:
    at the configuration's nprobe (half of these 64 lists) and at one
    list, where an IVF answer differs from the exact one and only the
    IVF reference can say what it must be. Ties aside: the generator's
    rows have no two equal distances to a query, so there are none."""
    w = world
    q = w.queries[:B] if queries == "pool" else between_lists(w)
    docs, _ = w.search(q, nprobe=nprobe)
    ids = np.array([[int(h["_id"][3:]) for h in row] for row in docs])
    scores = np.array([[h["_score"] for h in row] for row in docs])
    want_ids, want_d = ivfflat_reference.search(
        w.base, w.centroids, q, nprobe, w.k, lists=w.lists)
    assert (ids == want_ids).all()
    # tolerance, with its reason: the program computes |q|^2 - 2 q.v +
    # |v|^2 in float32 at `highest`; each term rounds at 2^-24 of
    # itself and they cancel down to the distance, so the error is a
    # few 1e-7 of |q|^2 + |v|^2 (2.4e-7 in the CPU rehearsal, 1.8-3.7e-7
    # on the chip for the rerank of the other configurations). The
    # configuration's limit, 3e-6, leaves that ten times of room and
    # sits four times under the nearest lower precision's 1.4e-5
    gap = np.abs(scores - want_d) / (
        (q.astype(np.float64) ** 2).sum(1)[:, None]
        + (w.base[want_ids].astype(np.float64) ** 2).sum(2))
    assert gap.max() <= w.cfg["limits"]["score_err_max"] == 3e-06
    if nprobe == 1:  # the probe regime is really what answered
        assert (want_ids != w.exact.topk(q, w.k)).any()


def test_served_cell_passes_its_own_comparison(world):
    """Every pool query through the served path at the cell's own
    parameters, judged as a run is: recall against the exact brute
    force, scores, short rows, under the configuration's limits."""
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


def bf16_piece(x, cut: bool):
    """The leading bfloat16 piece of float32, kept in float32: rounded
    to nearest even, or `cut` (the low 16 bits dropped)."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    if not cut:
        u = u + (((u >> 16) & 1) + 0x7FFF)
    return (u & 0xFFFF0000).astype(np.uint32).view(np.float32)


def scores_at_high(q, v, cut: bool):
    """Squared distances with the product as three bfloat16 passes
    (each operand split into two pieces, low x low dropped)."""
    qh, vh = bf16_piece(q, cut), bf16_piece(v, cut)
    ql, vl = bf16_piece(q - qh, cut), bf16_piece(v - vh, cut)
    dots = ((qh * vh).astype(np.float64).sum(1) + (qh * vl).sum(1)
            + (ql * vh).sum(1))
    return ((q.astype(np.float64) ** 2).sum(1) - 2 * dots
            + (v.astype(np.float64) ** 2).sum(1))


def test_scores_at_high_instead_of_highest_fail_score_err(world):
    """The share of the contract the chip run relies on: the same ids
    with the scan's product as `Precision.HIGH` computes it keep recall
    and fail `score_err`'s limit; the served scores pass it. The CPU
    backend computes float32 whatever a program asks for, so the lower
    precision is worked here in numpy, over the pool's 5,120 (query,
    row) pairs, the same number the chip's control sees. Pieces rounded
    to nearest read 2.96e-6 over them, a hair inside the limit and ten
    times the served scores' gap; the chip's own `high` reads 1.40e-5
    over as many pairs of this generator (PERF.md section 2), so its
    pieces are not rounded to nearest; cut, they read 3.1e-5. The
    limit lies under both of the chip's possible splits' readings at
    the cell's size; at this size the cut one shows it. On the chip
    `--control` runs `high` through jax at the cell's size."""
    w = world
    q_idx = np.arange(w.queries.shape[0])
    ids, scores = w.served(q_idx)
    q = w.queries[np.repeat(q_idx, w.k)]
    v = w.base[ids.ravel()]
    read = {}
    for name, served in (
            ("served", scores),
            ("nearest", scores_at_high(q, v, False).reshape(ids.shape)),
            ("cut", scores_at_high(q, v, True).reshape(ids.shape))):
        checks, _ = check.compare(w.cfg, w.exact, w.queries, w.truth,
                                  q_idx[None], ids[None], served[None])
        assert check.passed(checks["recall_at_10"]), checks
        read[name] = checks["score_err"]
    assert check.passed(read["served"]), read
    assert not check.passed(read["cut"]), read
    assert read["nearest"]["value"] > 8 * read["served"]["value"], read


def test_deleted_and_filtered_rows_are_never_returned(world):
    w = world
    q_idx = np.arange(B)
    ids, _ = w.served(q_idx)
    gone = [f"doc{i}" for i in sorted({int(r[0]) for r in ids})[:20]]
    assert w.client.delete(corpus.DB, w.space,
                           document_ids=gone) == len(gone)
    gone_rows = np.array([int(g[3:]) for g in gone])
    live = np.ones(ROWS, bool)
    live[gone_rows] = False
    after, _ = w.served(q_idx)
    assert not np.isin(after, gone_rows).any()
    want, _ = ivfflat_reference.search(
        w.base, w.centroids, w.queries[q_idx], w.params["nprobe"], w.k,
        lists=w.lists, allowed=live)
    assert (after == want).all()
    # a range filter on the scalar column: only rows that pass, and of
    # those the reference's (deleted rows stay out)
    flt = {"operator": "AND", "conditions": [
        {"field": "price", "operator": ">=", "value": 10.0},
        {"field": "price", "operator": "<", "value": 15.0}]}
    passing = live & (w.col >= 10.0) & (w.col < 15.0)
    filtered, _ = w.served(q_idx, filters=flt)
    assert passing[filtered].all()
    want, _ = ivfflat_reference.search(
        w.base, w.centroids, w.queries[q_idx], w.params["nprobe"], w.k,
        lists=w.lists, allowed=passing)
    assert (filtered == want).all()
    w.live = live


def test_a_padded_probe_slot_never_duplicates_a_docid(world, monkeypatch):
    """Host probe selection that comes up short (the HNSW coarse
    quantizer's way) pads with -1: the step scans list 0 for shape and
    masks every hit. Here half of each query's probes are padding, and
    list 0 itself is probed by some queries: no docid twice in a row,
    and the answer is the reference's over the lists really probed."""
    w = world
    nprobe = 8
    q_idx = np.arange(B)
    probed = ivfflat_reference.probes(w.queries[q_idx], w.centroids, nprobe)
    probed[: B // 2, 0] = 0  # list 0 probed for real, beside the padding
    probed[:, nprobe // 2:] = -1
    monkeypatch.setattr(
        w.index, "_host_probes",
        lambda q, n: np.ascontiguousarray(probed[: q.shape[0]], np.int32))
    ids, _ = w.served(q_idx, nprobe=nprobe)
    for row in ids:
        assert len(set(row.tolist())) == row.size
    want, _ = ivfflat_reference.search(
        w.base, w.centroids, w.queries[q_idx], nprobe, w.k, lists=w.lists,
        allowed=getattr(w, "live", None), probed=probed)
    assert (ids == want).all()


def _latest(spans):
    """The span that ended last. `tracing.snapshot()` walks every tracer
    the process still holds, in no order: under xdist an earlier test
    file's servers may come after this one's, so position says nothing."""
    return max(spans, key=lambda s: s.t1_ns)


def _ivf_gauges(w) -> dict:
    """`vearch_ps_ivf_publish{stat}` off the PS's /metrics, by stat."""
    import urllib.request

    with urllib.request.urlopen(f"http://{w.ps.addr}/metrics") as r:
        text = r.read().decode()
    return {line.split('stat="')[1].split('"')[0]:
            float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("vearch_ps_ivf_publish{")}


def test_an_appended_row_is_found_after_the_publish_it_forces(world):
    """One written row sets the index dirty; the next search absorbs it
    and re-publishes the whole table before it scans. The publish is a
    span with what it placed, and `/ps/stats` and `/metrics` count it."""
    from vearch_tpu.cluster import tracing

    w = world
    w.search(w.queries[:B])
    before = w.ivf_stats()
    vec = (w.queries[1] + 40.0).astype(np.float32)
    out = w.client.upsert(corpus.DB, w.space,
                          [{"_id": "bench_new", "emb": vec, "price": 1.0}])
    assert out["total"] == 1
    docs, _ = w.search(vec)
    assert docs[0][0]["_id"] == "bench_new"
    after = w.ivf_stats()
    assert after["publishes"] == before["publishes"] + 1
    assert after["rows"] == before["rows"] + 1 == ROWS + 1
    assert after["nlist"] == 64 and after["cap"] % TILE_ROWS == 0
    assert after["fill"] == pytest.approx(
        after["rows"] / (after["nlist"] * after["cap"]), abs=1e-6)
    publish = _latest(s for s in tracing.snapshot()
                      if s.name == "ivf.publish")
    assert {k: publish.tags[k] for k in
            ("rows", "nlist", "cap", "bytes", "fill")} == {
        k: after[k] for k in ("rows", "nlist", "cap", "bytes", "fill")}
    # the request that paid for it carries it, inside its probe phase
    probe = _latest(s for s in tracing.snapshot() if s.name == "ivf.probe"
                    and s.trace_id == publish.trace_id)
    assert probe.t0_ns <= publish.t0_ns and publish.t1_ns <= probe.t1_ns
    gauges = _ivf_gauges(w)
    assert sorted(gauges) == sorted(
        ["publishes", "rows", "nlist", "cap", "bytes", "fill", "seconds",
         "mask_builds", "mask_hits"])
    assert gauges["rows"] == ROWS + 1 and gauges["cap"] == after["cap"]
    assert gauges["fill"] == pytest.approx(after["fill"], abs=1e-6)
    assert w.client.delete(corpus.DB, w.space,
                           document_ids=["bench_new"]) == 1
    docs, _ = w.search(vec)
    assert all(h["_id"] != "bench_new" for h in docs[0])
    assert w.ivf_stats()["publishes"] == after["publishes"]  # a mask


def _probe_span(trace_id=None):
    from vearch_tpu.cluster import tracing

    return _latest(s for s in tracing.snapshot() if s.name == "ivf.probe"
                   and trace_id in (None, s.trace_id))


def test_the_same_mask_twice_is_one_build_and_one_hit(world):
    """The slot-major validity mask is built once per (published table,
    mask) and found again while the engine hands back the same mask: a
    delete makes the next search build one, the search after it hits.
    `/ps/stats`, the `ivf.probe` span and the gauge say which."""
    w = world
    q_idx = np.arange(B)
    ids, _ = w.served(q_idx)
    victim = int(ids[0, 0])
    assert w.client.delete(corpus.DB, w.space,
                           document_ids=[f"doc{victim}"]) == 1
    before = w.ivf_stats()
    ids, _ = w.served(q_idx)
    assert victim not in ids
    built = _probe_span()
    ids, _ = w.served(q_idx)
    assert victim not in ids
    hit = _probe_span()
    after = w.ivf_stats()
    assert built.tags["mask"] == "built" and hit.tags["mask"] == "hit"
    assert built.trace_id != hit.trace_id
    assert 0 <= hit.tags["mask_ms"] <= built.tags["mask_ms"]
    assert (after["mask_builds"], after["mask_hits"], after["publishes"]) == (
        before["mask_builds"] + 1, before["mask_hits"] + 1,
        before["publishes"])
    gauges = _ivf_gauges(w)
    assert (gauges["mask_builds"], gauges["mask_hits"]) == (
        after["mask_builds"], after["mask_hits"])
    w.live = getattr(w, "live", np.ones(ROWS, bool)).copy()
    w.live[victim] = False


def test_the_dispatch_span_carries_its_launch_and_the_probe_phase_its_tags(
        world):
    from vearch_tpu.cluster import tracing

    w = world
    w.search(w.queries[:B - 4])  # 60 rows in a 64 bucket
    spans = tracing.snapshot()
    kernel = _latest(s for s in spans if s.name == "kernel.ivfflat_scan")
    probe = _latest(s for s in spans if s.name == "ivf.probe")
    assert kernel.trace_id == probe.trace_id
    assert kernel.tags["rows"] == B - 4 and kernel.tags["bucket_rows"] == B
    assert 0 < kernel.tags["launch_us"] * 1e3 <= kernel.t1_ns - kernel.t0_ns
    stats = w.ivf_stats()
    assert probe.tags["nprobe"] == w.params["nprobe"] == 32
    assert probe.tags["cap"] == stats["cap"]
    assert probe.tags["fill"] == stats["fill"]
    assert probe.tags["mask"] in ("hit", "built")
    assert probe.tags["mask_ms"] * 1e6 <= probe.t1_ns - probe.t0_ns
    assert probe.t1_ns <= kernel.t0_ns + 1_000_000
    # the table was scanned in tiles: cap / 128 steps a probed list
    assert stats["cap"] > TILE_ROWS
    assert ivf_ops.probe_tile(stats["cap"], 128 * 4) == TILE_ROWS
    # and its scores selected after the loop, no sort wider than this
    assert probe.tags["select_width"] == perf_model.probe_select_width(
        w.params["rerank"], 32 * stats["cap"], TILE_ROWS)
