"""IVFFLAT / IVFPQ recall gates vs exact search — models the reference's
recall-baseline CI gates (reference: test/test_recall_baseline.py:301-303
recall@100>=0.9, @10>=0.8, @1>=0.5 vs an identical faiss build)."""

import numpy as np
import pytest

from vearch_tpu.engine.engine import Engine, SearchRequest
from vearch_tpu.engine.types import (
    DataType,
    FieldSchema,
    IndexParams,
    MetricType,
    TableSchema,
)

N, D = 8000, 32


def clustered_data(rng, n=N, d=D, n_clusters=80):
    """Gaussian-mixture dataset — the reference gates run on real datasets
    (SIFT/Glove) which are clustered; pure uniform gaussian noise is an
    IVF pathology, not a correctness signal."""
    centers = rng.standard_normal((n_clusters, d)).astype(np.float32) * 4
    which = rng.integers(0, n_clusters, n)
    return (centers[which]
            + 0.6 * rng.standard_normal((n, d)).astype(np.float32))


def build_engine(index_type, metric=MetricType.L2, params=None, rng=None):
    base_params = {"ncentroids": 64, "nprobe": 16, "training_threshold": 1000}
    base_params.update(params or {})
    schema = TableSchema(
        name="ivf",
        fields=[
            FieldSchema("emb", DataType.VECTOR, dimension=D,
                        index=IndexParams(index_type, metric, base_params)),
        ],
    )
    eng = Engine(schema)
    vecs = clustered_data(rng)
    eng.upsert([{"_id": f"d{i}", "emb": vecs[i]} for i in range(N)])
    eng.wait_for_index()
    eng.build_index()  # ensure trained + absorbed even if threshold logic races
    return eng, vecs


def exact_topk(vecs, queries, k, metric):
    if metric is MetricType.L2:
        d = ((queries[:, None] - vecs[None]) ** 2).sum(-1)
        return np.argsort(d, axis=1)[:, :k]
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    vn = vecs / np.linalg.norm(vecs, axis=1, keepdims=True)
    if metric is MetricType.COSINE:
        return np.argsort(-(qn @ vn.T), axis=1)[:, :k]
    return np.argsort(-(queries @ vecs.T), axis=1)[:, :k]


def recall_at(eng, vecs, queries, k, metric, nprobe=None):
    ref = exact_topk(vecs, queries, k, metric)
    req = SearchRequest(vectors={"emb": queries}, k=k,
                        index_params={"nprobe": nprobe} if nprobe else {})
    res = eng.search(req)
    hits = 0
    for qi, r in enumerate(res):
        got = {int(it.key[1:]) for it in r.items}
        hits += len(got & set(ref[qi].tolist()))
    return hits / (len(res) * k)


@pytest.mark.parametrize("index_type", ["IVFFLAT", "IVFPQ"])
def test_recall_gates_l2(index_type, rng):
    eng, vecs = build_engine(index_type, rng=rng)
    queries = vecs[rng.choice(N, 50, replace=False)] + \
        0.01 * rng.standard_normal((50, D)).astype(np.float32)
    assert recall_at(eng, vecs, queries, 1, MetricType.L2) >= 0.5
    assert recall_at(eng, vecs, queries, 10, MetricType.L2) >= 0.8
    assert recall_at(eng, vecs, queries, 100, MetricType.L2) >= 0.9


def test_ivfflat_full_probe_is_exact(rng):
    """nprobe == nlist must reproduce the exact result set (no rerank loss)."""
    eng, vecs = build_engine("IVFFLAT", rng=rng)
    queries = vecs[:20]
    r = recall_at(eng, vecs, queries, 10, MetricType.L2, nprobe=64)
    assert r == 1.0


def test_ivfpq_scores_are_exact_after_rerank(rng):
    """Rerank recomputes exact distances: reported scores must match the
    true L2 distance (reference exactness invariant on reranked paths)."""
    eng, vecs = build_engine("IVFPQ", rng=rng)
    q = vecs[7:8]
    res = eng.search(SearchRequest(vectors={"emb": q}, k=5))
    for it in res[0].items:
        true_d = float(((vecs[int(it.key[1:])] - q[0]) ** 2).sum())
        assert it.score == pytest.approx(true_d, rel=1e-3, abs=1e-2)


def test_ivf_cosine_metric(rng):
    eng, vecs = build_engine("IVFFLAT", metric=MetricType.COSINE, rng=rng)
    queries = vecs[rng.choice(N, 30, replace=False)]
    assert recall_at(eng, vecs, queries, 10, MetricType.COSINE) >= 0.8


def test_ivf_realtime_absorb_after_build(rng):
    """Docs added after the index is built must be searchable (realtime
    ingest pump; reference: AddRTVecsToIndex)."""
    eng, vecs = build_engine("IVFFLAT", rng=rng)
    new = rng.standard_normal((10, D)).astype(np.float32) + 5.0
    eng.upsert([{"_id": f"new{i}", "emb": new[i]} for i in range(10)])
    res = eng.search(SearchRequest(vectors={"emb": new[:3]}, k=1))
    assert [r.items[0].key for r in res] == ["new0", "new1", "new2"]


def test_ivf_delete_masked(rng):
    eng, vecs = build_engine("IVFFLAT", rng=rng)
    res = eng.search(SearchRequest(vectors={"emb": vecs[3:4]}, k=1))
    assert res[0].items[0].key == "d3"
    eng.delete(["d3"])
    res = eng.search(SearchRequest(vectors={"emb": vecs[3:4]}, k=5))
    assert all(it.key != "d3" for it in res[0].items)


def test_training_threshold_background_build(rng):
    """Auto-build must trigger once doc count crosses training_threshold."""
    schema = TableSchema(
        name="auto",
        fields=[
            FieldSchema("emb", DataType.VECTOR, dimension=D,
                        index=IndexParams("IVFFLAT", MetricType.L2,
                                          {"ncentroids": 16,
                                           "training_threshold": 500})),
        ],
    )
    eng = Engine(schema)
    vecs = rng.standard_normal((600, D)).astype(np.float32)
    eng.upsert([{"_id": f"d{i}", "emb": vecs[i]} for i in range(600)])
    eng.wait_for_index(timeout=60)
    idx = eng.indexes["emb"]
    assert idx.trained
    assert idx.indexed_count >= 500


def test_ivfpq_dump_load_preserves_search(rng, tmp_path):
    eng, vecs = build_engine("IVFPQ", rng=rng)
    eng.dump(str(tmp_path / "pq"))
    eng2 = Engine.open(str(tmp_path / "pq"))
    assert eng2.indexes["emb"].trained
    res = eng2.search(SearchRequest(vectors={"emb": vecs[11:12]}, k=3))
    assert res[0].items[0].key == "d11"


def _full_scores(scan, queries, rows, scale, vsq, valid, metric):
    """The [B, N] f32 score matrix a full scan hands `_select_topk`,
    built from the scan's own pieces: what the selection is compared
    with `lax.top_k` on."""
    import jax
    import jax.numpy as jnp

    from vearch_tpu.ops.binary_scan import _binary_scores
    from vearch_tpu.ops.distance import sqnorms
    from vearch_tpu.ops.ivf import NEG_INF, unpack_int4

    @jax.jit
    def scores():
        if scan == "binary":
            return _binary_scores(queries, rows, scale, vsq, valid, metric)
        vals = rows.astype(jnp.bfloat16) if scan == "int8" \
            else unpack_int4(rows)
        dots = jax.lax.dot_general(
            queries.astype(jnp.bfloat16), vals, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale[None, :]
        if metric is MetricType.L2:
            out = -(sqnorms(queries)[:, None] - 2.0 * dots + vsq[None, :])
        else:
            out = dots
        return jnp.where(valid[None, :], out, NEG_INF)

    return scores()


def _plain_topk(scores, r):
    """`lax.top_k` over the row, masked slots as id -1."""
    import jax

    s, i = map(np.asarray, jax.lax.top_k(scores, r))
    return s, np.where(np.isfinite(s), i, -1)


def test_int8_scan_blockmax_matches_exact():
    """The block-max two-stage top-k returns the same top candidates as
    exact lax.top_k on a well-separated dataset (id reconstruction
    across blocks is the failure mode to catch), at a row count below
    the one from which `_select_topk` takes it of itself."""
    import jax.numpy as jnp

    from vearch_tpu.ops.ivf import _blocked_topk

    rng = np.random.default_rng(7)
    n, d = 512 * 64, 32  # 256 blocks of 128 rows, 32 of them gathered
    base = rng.integers(-100, 100, (n, d)).astype(np.int8)
    scale = np.ones(n, np.float32)
    vsq = np.sum((base.astype(np.float32)) ** 2, axis=1)
    valid = np.ones(n, bool)
    q = base[rng.choice(n, 8, replace=False)].astype(np.float32)

    scores = _full_scores("int8", *map(jnp.asarray,
                                       (q, base, scale, vsq, valid)),
                          MetricType.L2)
    es, ei = _plain_topk(scores, 32)
    bs, bi = map(np.asarray, _blocked_topk(scores, 32))
    # top-1 self-match must survive block selection exactly
    np.testing.assert_array_equal(ei[:, 0], bi[:, 0])
    # the two-stage selection is exact: the same scores, and the same
    # ids wherever integer scores do not tie
    np.testing.assert_array_equal(es, bs)
    for row in range(8):
        overlap = len(set(ei[row, :10].tolist()) & set(bi[row, :10].tolist()))
        assert overlap >= 9, (row, overlap)


def test_blockmax_never_resurrects_filtered_docs():
    """Selective filter + blockmax: masked slots must come back as id=-1,
    never as real docids that rerank could rescore into results (review
    r2 finding — exact_rerank masks only id>=0, not validity)."""
    import jax.numpy as jnp

    from vearch_tpu.ops.ivf import BLOCK, _blocked_topk, int8_scan_candidates

    rng = np.random.default_rng(3)
    # 512 blocks: the fewest from which r=128 selects in two stages;
    # 40 rows more leave a ragged block and the plain top-k
    for n in (512 * BLOCK, 512 * BLOCK + 40):
        d = 16
        base = rng.integers(-100, 100, (n, d)).astype(np.int8)
        vsq = np.sum(base.astype(np.float32) ** 2, axis=1)
        valid = np.zeros(n, bool)
        allowed = rng.choice(n, 40, replace=False)
        valid[allowed] = True  # only 40 of 65k docs pass the filter
        q = rng.standard_normal((4, d)).astype(np.float32)
        s, i = int8_scan_candidates(
            jnp.asarray(q), jnp.asarray(base),
            jnp.asarray(np.ones(n, np.float32)), jnp.asarray(vsq),
            jnp.asarray(valid), 128, MetricType.L2)
        s, i = np.asarray(s), np.asarray(i)
        real = i[i >= 0]
        assert set(real.tolist()) <= set(allowed.tolist()), n
        # every -inf slot is id -1
        assert np.all(i[~np.isfinite(s)] == -1), n

    # the two-stage selection on a tiny space (fewer blocks than r)
    # degrades gracefully, no crash
    s, _ = _blocked_topk(
        jnp.asarray(rng.standard_normal((4, 1024)).astype(np.float32)), 128)
    assert np.asarray(s).shape == (4, 128)


def _scan_args(rng, n, d, b, selective):
    """int8 rows whose own copies are the queries (self-match first),
    per-row scales, and a mask that passes every row or one in ten."""
    import jax.numpy as jnp

    base = rng.integers(-100, 100, (n, d)).astype(np.int8)
    scale = rng.uniform(0.5, 1.5, n).astype(np.float32)
    vsq = np.sum((base.astype(np.float32) * scale[:, None]) ** 2, axis=1)
    valid = (rng.random(n) < 0.1) if selective else np.ones(n, bool)
    q = base[rng.choice(n, b, replace=False)].astype(np.float32)
    return tuple(jnp.asarray(a) for a in (q, base, scale, vsq, valid))


# 2,100 blocks of 128 rows: the scan takes the two-stage selection from
# 4 * max(r, 128) = 1,024 blocks, and the top 256 of a row lie in more
# blocks than a capped selection keeps (136 blocks of 512 until PR 26);
# +40 rows leave a ragged last block, which it answers with the plain
# top-k
@pytest.mark.parametrize("selective", [False, True])
@pytest.mark.parametrize("n", [128 * 2100, 128 * 2100 + 40])
@pytest.mark.parametrize("metric",
                         [MetricType.L2, MetricType.INNER_PRODUCT])
@pytest.mark.parametrize("b", [8, 64])
def test_scan_candidates_are_the_exact_top_r(b, metric, n, selective):
    """The scan returns `lax.top_k` over the full row of its score
    matrix: the same scores in the same order, the same ids wherever
    scores do not tie, no id twice, masked slots as -1."""
    from vearch_tpu.ops.ivf import int8_scan_candidates

    r = 256
    args = _scan_args(np.random.default_rng(b + n), n, 16, b, selective)
    es, ei = _plain_topk(_full_scores("int8", *args, metric), r)
    s, i = map(np.asarray, int8_scan_candidates(*args, r, metric))
    np.testing.assert_array_equal(s, es)
    # rows of equal score may come back in another order
    tied = (s == np.roll(s, 1, 1)) | (s == np.roll(s, -1, 1))
    assert np.all((i == ei) | tied)
    assert np.all((i == -1) == ~np.isfinite(s))
    assert np.all(np.asarray(args[4])[i[i >= 0]]), "a masked row came back"
    for row in range(b):
        real = i[row][i[row] >= 0]
        assert len(set(real.tolist())) == len(real), "an id twice"


@pytest.mark.parametrize("b", [5, 8, 64])
def test_select_topk_on_tied_scores(b):
    """Scores drawn from 50 values tie everywhere: the selection still
    returns the top-r multiset of every row and ids that hold exactly
    those scores. b=5 is no multiple of the 8-row tile."""
    import jax
    import jax.numpy as jnp

    from vearch_tpu.ops.ivf import BLOCK, NEG_INF, _select_topk

    rng = np.random.default_rng(b)
    n, r = BLOCK * 2100, 256
    scores = rng.integers(0, 50, (b, n)).astype(np.float32)
    scores[:, rng.choice(n, n // 3, replace=False)] = NEG_INF
    scores[0] = NEG_INF  # a row with fewer live slots than r
    scores[0, 120:200] = rng.integers(0, 50, 80)
    es, _ = jax.lax.top_k(jnp.asarray(scores), r)
    s, i = map(np.asarray, _select_topk(jnp.asarray(scores), r))
    np.testing.assert_array_equal(s, np.asarray(es))
    rows = np.arange(b)[:, None]
    live = i >= 0
    np.testing.assert_array_equal(
        scores[rows, np.maximum(i, 0)][live], s[live])
    assert np.all(~np.isfinite(s[~live])) and live[0].sum() == 80
    for row in range(b):
        assert len(set(i[row][live[row]].tolist())) == live[row].sum()


def _selection_input(pattern, b, n, r, rng):
    """[b, n] f32 scores that put the top-r of a row where a level of
    `_blocked_topk` could lose it: 128-score blocks, then groups of 8
    consecutive lanes of a gathered block."""
    from vearch_tpu.ops.ivf import BLOCK, GROUP

    x = rng.standard_normal((b, n)).astype(np.float32)
    nblk = n // BLOCK
    if pattern == "one_block":
        # a docid-ordered cluster: the whole top-r in adjacent blocks,
        # every group of them full of top scores
        x[:, 5 * BLOCK:5 * BLOCK + r] += 100.0
    elif pattern == "few_groups":
        # the third group of r / 4 blocks raised: twice the r / 8
        # groups that could hold the top-r, eight top scores each, and
        # half of them must lose
        for j in rng.choice(nblk, r // 4, replace=False):
            x[:, j * BLOCK + 2 * GROUP:j * BLOCK + 3 * GROUP] += 100.0
    elif pattern == "one_lane":
        # lane 17 of 2 r blocks: one top score a block and a group,
        # more raised blocks than the first level keeps
        x[:, rng.choice(nblk, 2 * r, replace=False) * BLOCK + 17] += 100.0
    elif pattern == "ties":
        # nine values: the r-th score ties with thousands
        x = rng.integers(0, 9, (b, n)).astype(np.float32)
    elif pattern == "few_finite":
        live = rng.choice(n, r - 50, replace=False)
        keep = x[:, live]
        x[:] = -np.inf
        x[:, live] = keep
    elif pattern == "inf_blocks":
        # r / 2 live blocks: half the gathered blocks are all -inf
        dead = np.ones(nblk, bool)
        dead[rng.choice(nblk, r // 2, replace=False)] = False
        x.reshape(b, nblk, BLOCK)[:, dead] = -np.inf
    else:
        assert pattern == "random"
    return x


@pytest.mark.parametrize("pattern", [
    "random", "one_block", "few_groups", "one_lane", "ties",
    "few_finite", "inf_blocks"])
@pytest.mark.parametrize("r", [128, 256, 512])
@pytest.mark.parametrize("b", [5, 8, 64])
def test_select_topk_is_exact_at_every_level(b, r, pattern):
    """`_select_topk` against numpy's sort of the row, at the fewest
    blocks from which r selects in levels: the same multiset of scores
    in descending order, ids that hold exactly those scores, none
    twice, masked slots -1."""
    import jax
    import jax.numpy as jnp

    from vearch_tpu.ops.ivf import BLOCK, _select_topk

    n = BLOCK * 4 * r
    x = _selection_input(pattern, b, n, r, np.random.default_rng(b * r))
    s, i = map(np.asarray, jax.jit(_select_topk, static_argnums=1)(
        jnp.asarray(x), r))
    np.testing.assert_array_equal(s, -np.sort(-x, axis=1)[:, :r])
    live = np.isfinite(s)
    assert np.all(i[~live] == -1) and np.all(i[live] >= 0)
    rows = np.arange(b)[:, None]
    np.testing.assert_array_equal(x[rows, np.maximum(i, 0)][live], s[live])
    for row in range(b):
        assert len(set(i[row][live[row]].tolist())) == live[row].sum()


@pytest.mark.parametrize("scan", ["int4", "binary"])
def test_other_full_scans_select_their_exact_top_r(scan):
    """int4 and the 1-bit stage-0 scan hand their own [B, N] scores to
    the same selection: at 512 blocks, where r=48 selects in two
    stages, each returns its exact top-k."""
    import jax.numpy as jnp

    from vearch_tpu.index.int8_mirror import quantize_rows_int4
    from vearch_tpu.ops.binary_scan import (
        binary_scan_candidates, pack_sign_rows)
    from vearch_tpu.ops.ivf import int4_scan_candidates

    rng = np.random.default_rng(11)
    n, d, b, r = 128 * 512, 32, 8, 48
    rows = rng.standard_normal((n, d)).astype(np.float32)
    q = jnp.asarray(rows[rng.choice(n, b, replace=False)])
    valid = jnp.asarray(rng.random(n) < 0.5)
    if scan == "int4":
        packed, scale, vsq = quantize_rows_int4(rows)
        fn = int4_scan_candidates
    else:
        packed, scale, vsq = pack_sign_rows(rows)
        fn = binary_scan_candidates
    args = (q, jnp.asarray(packed), jnp.asarray(scale), jnp.asarray(vsq),
            valid)
    es, ei = _plain_topk(_full_scores(scan, *args, MetricType.L2), r)
    s, i = map(np.asarray, fn(*args, r, MetricType.L2))
    # the matrix is another program's: its scores round an ulp apart
    np.testing.assert_allclose(s, es, rtol=1e-6, atol=1e-5)
    assert np.mean(i == ei) > 0.99  # all but rows of equal score


class TestHnswCoarseQuantizer:
    """quantizer_type=hnsw (reference: gamma_index_ivfpq.h:1258-1329
    quantizer_type_ — HNSW over the centroids replaces the flat coarse
    scan; here the graph runs on HOST so probe selection costs no
    device dispatch)."""

    def _data(self, n=20_000, d=32):
        rng = np.random.default_rng(17)
        centers = (rng.standard_normal((150, d)) * 3).astype(np.float32)
        base = centers[rng.integers(0, 150, n)] + \
            0.6 * rng.standard_normal((n, d)).astype(np.float32)
        return base

    def _engine(self, base, extra=None):
        from vearch_tpu.engine.engine import Engine

        schema = TableSchema("hq", [
            FieldSchema("v", DataType.VECTOR, dimension=base.shape[1],
                        index=IndexParams("IVFPQ", MetricType.L2, {
                            "ncentroids": 128, "nsubvector": 8,
                            "train_iters": 5, "training_threshold":
                            base.shape[0], "scan_mode": "probe",
                            "nprobe": 24, "quantizer_type": "hnsw",
                            **(extra or {}),
                        })),
        ])
        eng = Engine(schema)
        n = base.shape[0]
        for i in range(0, n, 10_000):
            eng.upsert([{"_id": str(j), "v": base[j]}
                        for j in range(i, min(i + 10_000, n))])
        eng.build_index()
        return eng

    def test_probe_recall_matches_flat_quantizer(self):
        import pytest

        from vearch_tpu.engine.engine import SearchRequest
        from vearch_tpu.native.hnsw_graph import HnswGraph, _load

        if _load() is None:
            pytest.skip("no native toolchain")
        base = self._data()
        eng = self._engine(base)
        idx = eng.indexes["v"]
        assert idx.quantizer_type == "hnsw"
        assert idx._coarse_graph is not None

        rng = np.random.default_rng(5)
        q = base[:48] + 0.05 * rng.standard_normal(
            (48, base.shape[1])).astype(np.float32)
        exact = np.argsort(
            ((q[:, None, :].astype(np.float64)
              - base[None, :, :].astype(np.float64)) ** 2).sum(-1),
            axis=1)[:, :10]
        res = eng.search(SearchRequest(vectors={"v": q}, k=10,
                                       include_fields=[],
                                       index_params={"rerank": 256}))
        got = [[int(it.key) for it in r.items] for r in res]
        r10 = float(np.mean([
            len(set(got[i]) & set(exact[i].tolist())) / 10
            for i in range(48)
        ]))
        assert r10 >= 0.8, r10

    def test_hnsw_assignment_close_to_exact(self):
        import pytest

        from vearch_tpu.native.hnsw_graph import _load
        from vearch_tpu.ops import kmeans as km

        if _load() is None:
            pytest.skip("no native toolchain")
        base = self._data(n=8000)
        eng = self._engine(base)
        idx = eng.indexes["v"]
        rows = base[:2000]
        import jax.numpy as jnp

        exact = np.asarray(km.assign_clusters(jnp.asarray(rows),
                                              idx.centroids))
        graph = idx._assign(rows)
        agreement = float(np.mean(exact == graph))
        assert agreement >= 0.95, agreement

    def test_dump_load_rebuilds_graph(self, tmp_path):
        import pytest

        from vearch_tpu.engine.engine import Engine, SearchRequest
        from vearch_tpu.native.hnsw_graph import _load

        if _load() is None:
            pytest.skip("no native toolchain")
        base = self._data(n=8000)
        eng = self._engine(base)
        eng.dump(str(tmp_path))
        eng2 = Engine.open(str(tmp_path))
        idx2 = eng2.indexes["v"]
        assert idx2._coarse_graph is not None
        res = eng2.search(SearchRequest(vectors={"v": base[7]}, k=3,
                                        include_fields=[]))
        assert res[0].items[0].key == "7"

    def test_fallback_to_flat_without_native(self, monkeypatch):
        """The PRODUCTION except-branch runs: HnswGraph construction
        raising RuntimeError (no toolchain) must degrade to the flat
        quantizer, not crash training."""
        import vearch_tpu.native.hnsw_graph as hg

        class Unavailable:
            def __init__(self, *a, **kw):
                raise RuntimeError("native HNSW unavailable (forced)")

        monkeypatch.setattr(hg, "HnswGraph", Unavailable)
        base = self._data(n=6000)
        eng = self._engine(base)
        idx = eng.indexes["v"]
        assert idx.quantizer_type == "flat"
        from vearch_tpu.engine.engine import SearchRequest

        res = eng.search(SearchRequest(vectors={"v": base[3]}, k=3,
                                       include_fields=[]))
        assert res[0].items[0].key == "3"


def test_padded_probe_slots_never_duplicate_results():
    """A probes row containing -1 padding must not scan a real cell
    twice: no docid may appear more than once in the top-k."""
    import jax.numpy as jnp

    from vearch_tpu.ops import ivf as ivf_ops

    rng = np.random.default_rng(3)
    nlist, cap, d = 4, 8, 16
    cents = rng.standard_normal((nlist, d)).astype(np.float32)
    vecs = rng.standard_normal((nlist, cap, d)).astype(np.float32)
    ids = np.arange(nlist * cap, dtype=np.int32).reshape(nlist, cap)
    sqn = (vecs ** 2).sum(-1).astype(np.float32)
    ok = np.ones((nlist, cap), dtype=bool)  # slot-major, as the table
    q = rng.standard_normal((3, d)).astype(np.float32)
    # every row probes cell 2 once plus two padded slots
    probes = np.array([[2, -1, -1]] * 3, dtype=np.int32)
    scores, out = ivf_ops.ivfflat_candidates(
        jnp.asarray(q), jnp.asarray(cents), jnp.asarray(vecs),
        jnp.asarray(sqn), jnp.asarray(ids), jnp.asarray(ok),
        3, 16, MetricType.L2, probes=jnp.asarray(probes),
    )
    out = np.asarray(out)
    for row in out:
        real = row[row >= 0]
        assert len(real) == len(set(real.tolist())), row
        # only cell 2's docids can appear
        assert all(16 <= i < 24 for i in real), row


def _probe_table(rng, nlist, cap, d):
    """A padded [nlist, cap, d] table as `IVFFlatIndex` publishes one:
    lists of unequal length over shuffled docids, `-1` beyond a list's
    end, and ROWS in the padding that would win if they were scored."""
    lengths = rng.integers(cap // 4, cap + 1, nlist)
    lengths[0] = cap  # one full list, as the longest always is
    docids = rng.permutation(int(lengths.sum())).astype(np.int32)
    ids = np.full((nlist, cap), -1, np.int32)
    at = 0
    for c, n in enumerate(lengths):
        ids[c, :n] = docids[at:at + n]
        at += n
    cents = rng.standard_normal((nlist, d)).astype(np.float32) * 3
    vecs = (cents[:, None, :]
            + rng.standard_normal((nlist, cap, d)).astype(np.float32))
    return cents, vecs, ids


def _plain_probe_scan(q, cents, vecs, ids, ok, nprobe, r, metric, probes):
    """ivfflat_candidates in plain numpy, float64: the nprobe lists
    nearest each query (or the ones handed in, `-1` skipped), every
    slot the mask lets through scored, the best r kept."""
    q64, v64 = q.astype(np.float64), vecs.astype(np.float64)
    if probes is None:
        c64 = cents.astype(np.float64)
        coarse = 2 * q64 @ c64.T - (c64 ** 2).sum(1)[None]
        probes = np.argsort(-coarse, axis=1, kind="stable")[:, :nprobe]
    out_i = np.full((q.shape[0], r), -1, np.int64)
    out_s = np.full((q.shape[0], r), -np.inf)
    for b in range(q.shape[0]):
        lists = [c for c in probes[b] if c >= 0]
        cand_i = np.concatenate([ids[c][ok[c]] for c in lists])
        cand_v = np.concatenate([v64[c][ok[c]] for c in lists])
        if metric is MetricType.L2:
            s = -((q64[b][None] - cand_v) ** 2).sum(1)
        else:
            s = cand_v @ q64[b]
        order = np.argsort(-s, kind="stable")[:r]
        out_i[b, :order.size] = cand_i[order]
        out_s[b, :order.size] = s[order]
    return out_s, out_i


@pytest.mark.parametrize("case", [
    "all_alive", "mask_60pct", "mask_2pct", "all_false", "several_tiles",
    "padded_probe_slots", "inner_product", "tied_rows"])
def test_probe_scan_under_the_slot_major_mask_is_the_plain_reference(
        case, monkeypatch):
    """`ivfflat_candidates` takes its validity mask in the table's own
    order, `[nlist, cap]`, true where a slot holds a row that is alive
    and passes the filter: the ids and scores are the plain numpy
    reference's whatever share of the slots the mask lets through,
    however many tiles a list is scanned in, with `-1` probe slots and
    under inner product. The padding holds rows (`_probe_table`): a
    slot scored where the mask says false would be served. Rows of equal
    score come in the order they were scanned in (`tied_rows`), whatever
    their ids."""
    import jax.numpy as jnp

    from vearch_tpu.ops import ivf as ivf_ops

    rng = np.random.default_rng(33)
    nlist, d, b, nprobe, r = 8, 16, 5, 3, 16
    tiled = case in ("several_tiles", "tied_rows")
    cap = 48 if tiled else 32
    cents, vecs, ids = _probe_table(rng, nlist, cap, d)
    # one row four times: twice in the first tile of list 0, once in its
    # second tile, once in list 5, each copy under a smaller id than the
    # one scanned before it
    copies = ([0, 0, 0, 5], [3, 7, 19, 2])
    if case == "tied_rows":
        vecs[copies] = vecs[0, 3]
        ids[copies] = np.sort(ids[copies])[::-1]
    n = int(ids.max()) + 1
    share = {"mask_60pct": 0.6, "mask_2pct": 0.02, "all_false": 0.0}
    valid = rng.random(n) < share.get(case, 1.0)
    # what the index builds: one scatter over rows, padding stays false
    ok = (ids >= 0) & valid[np.maximum(ids, 0)]
    assert ok.sum() == valid.sum()
    metric = (MetricType.INNER_PRODUCT if case == "inner_product"
              else MetricType.L2)
    q = (cents[rng.integers(0, nlist, b)]
         + rng.standard_normal((b, d)).astype(np.float32))
    probes = None
    if case == "padded_probe_slots":
        probes = np.array([[2, -1, 5], [-1, -1, 0], [7, 1, -1],
                           [0, -1, -1], [3, 4, 6]], np.int32)
    if case == "tied_rows":
        # query 0 is the row: its four copies tie for the first places
        q[0] = vecs[0, 3]
        probes = np.array([[0, 5, 1]] * b, np.int32)
    if tiled:
        monkeypatch.setattr(ivf_ops, "PROBE_SLICE_BYTES", 16 * d * 4)
        assert ivf_ops.probe_tile(cap, d * 4) == 16  # three steps a list
    scores, out = ivf_ops.ivfflat_candidates(
        jnp.asarray(q), jnp.asarray(cents), jnp.asarray(vecs),
        jnp.asarray((vecs ** 2).sum(-1)), jnp.asarray(ids),
        jnp.asarray(ok), nprobe, r, metric,
        probes=None if probes is None else jnp.asarray(probes))
    want_s, want_i = _plain_probe_scan(q, cents, vecs, ids, ok, nprobe, r,
                                       metric, probes)
    assert (np.asarray(out) == want_i).all()
    got_s = np.asarray(scores, np.float64)
    found = want_i >= 0
    assert (got_s[~found] == -np.inf).all()
    np.testing.assert_allclose(got_s[found], want_s[found], rtol=2e-5,
                               atol=2e-4)
    if case == "all_false":
        assert not found.any()
    elif case == "mask_2pct":  # fewer rows pass than the depth asks for
        assert found.any() and not found.all()
    else:
        assert found.all()
    if case == "tied_rows":  # scan order, which is descending id order
        assert np.asarray(out)[0, :4].tolist() == ids[copies].tolist()
        assert len(set(np.asarray(scores)[0, :4].tolist())) == 1


def _total_order_key(scores):
    """The int32 key `lax.top_k` orders f32 scores by, in numpy: the
    bits, negative ones flipped (-0.0 below 0.0, -inf below all)."""
    bits = np.asarray(scores, np.float32).view(np.int32)
    return np.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _scan_order_reference(q, vecs, ids, ok, probes, r, metric):
    """`ivfflat_candidates` in plain numpy over integer-valued rows,
    where every float32 score is exact: each query's row of scores in
    scan order (its probes in order, a list's slots in order; -inf where
    the mask says false or the probe slot is -1), a stable argsort of
    the row's descending total-order keys, the best r kept."""
    b = q.shape[0]
    pr = np.maximum(probes, 0)
    v = vecs[pr]  # [B, nprobe, cap, d]
    dots = np.einsum("bd,bpcd->bpc", q, v)
    if metric is MetricType.L2:
        s = -((q ** 2).sum(1)[:, None, None] - 2 * dots + (v ** 2).sum(-1))
    else:
        s = dots
    live = ok[pr] & (probes >= 0)[:, :, None]
    s = np.where(live, s, -np.inf).astype(np.float32).reshape(b, -1)
    key = _total_order_key(s).astype(np.int64)
    order = np.argsort(-key, axis=1, kind="stable")[:, :r]
    top = np.take_along_axis(s, order, axis=1)
    got = np.take_along_axis(ids[pr].reshape(b, -1), order, axis=1)
    if r > s.shape[1]:
        top = np.pad(top, ((0, 0), (0, r - s.shape[1])),
                     constant_values=-np.inf)
        got = np.pad(got, ((0, 0), (0, r - s.shape[1])))
    return top, np.where(np.isfinite(top), got, -1)


@pytest.mark.parametrize("case,b,nprobe,cap,r", [
    ("blocked_cap4096", 64, 32, 4096, 256),
    ("blocked_cap8192", 8, 32, 8192, 256),
    ("blocked_r10", 5, 32, 4096, 10),
    ("blocked_inner_product", 8, 32, 2048, 64),
    ("blocked_padded_probe_slots", 8, 32, 4096, 256),
    ("blocked_all_masked", 5, 32, 2048, 64),
    ("top_k_r2048", 8, 32, 4096, 2048),
    ("top_k_small_table", 5, 3, 48, 16),
    ("top_k_depth_past_the_row", 8, 3, 40, 128),
])
def test_probe_selection_is_a_stable_argsort_of_scan_order(
        case, b, nprobe, cap, r, monkeypatch):
    """`ivfflat_candidates` selects after its loop, in levels of maxima
    where the row is long enough (`perf_model.probe_select_blocked`),
    else by one `top_k`: either way its ids and score bits are those of
    a stable descending argsort of each query's scan-order total-order
    keys. Rows in {-1, 0, 1} tie everywhere: inside a block of 128, across
    blocks, groups of 8, scan steps and lists; one row is copied into
    three lists and twice into one list's later tile, each copy under a
    smaller id than the one scanned before it, so a tie broken by id or
    by level order would serve another row. An exact match scores -0.0
    under L2, and query 0 matches the copies alone."""
    import jax.numpy as jnp

    from vearch_tpu.ops import ivf as ivf_ops
    from vearch_tpu.ops import perf_model

    rng = np.random.default_rng(cap + r + b)
    nlist, d = max(nprobe, 8) + 8, 4
    monkeypatch.setattr(ivf_ops, "PROBE_SLICE_BYTES", 1024 * d * 4)
    tile = ivf_ops.probe_tile(cap, d * 4)
    blocked = perf_model.probe_select_blocked(min(r, nprobe * cap),
                                              nprobe * cap, tile)
    assert blocked == case.startswith("blocked"), (case, tile)
    vecs = rng.integers(-1, 2, (nlist, cap, d)).astype(np.float32)
    lengths = rng.integers(cap // 3, cap + 1, nlist)
    lengths[:6] = cap
    ids = np.full((nlist, cap), -1, np.int32)
    slot_ok = np.arange(cap)[None, :] < lengths[:, None]
    ids[slot_ok] = rng.permutation(int(slot_ok.sum())).astype(np.int32)
    copies = ([0, 0, 0, 3, 5], [3, 7, cap - 2, 1, cap // 2])
    vecs[copies] = 2.0  # a row no other slot holds
    ids[copies] = np.sort(ids[copies])[::-1]
    ok = slot_ok & (rng.random((nlist, cap)) < 0.7)
    ok[copies] = True
    if case == "blocked_all_masked":
        ok[:] = False
    metric = (MetricType.INNER_PRODUCT if "inner_product" in case
              else MetricType.L2)
    probes = np.stack([rng.permutation(nlist)[:nprobe]
                       for _ in range(b)]).astype(np.int32)
    probes[0, :3] = [0, 3, 5]  # query 0 scans the copies' lists first
    q = rng.integers(-1, 2, (b, d)).astype(np.float32)
    q[0] = vecs[0, 3]
    if case == "blocked_padded_probe_slots":
        probes[1, 5:] = -1
        probes[2, :] = -1  # a query with nothing to scan
        probes[3, ::2] = -1
    s, i = map(np.asarray, ivf_ops.ivfflat_candidates(
        jnp.asarray(q), jnp.zeros((nlist, d)), jnp.asarray(vecs),
        jnp.asarray((vecs ** 2).sum(-1)), jnp.asarray(ids),
        jnp.asarray(ok), nprobe, r, metric, probes=jnp.asarray(probes)))
    want_s, want_i = _scan_order_reference(q, vecs, ids, ok, probes, r,
                                           metric)
    assert s.shape == i.shape == (b, r)
    np.testing.assert_array_equal(i, want_i)
    np.testing.assert_array_equal(s.view(np.int32), want_s.view(np.int32))
    if case == "blocked_all_masked":
        assert (i == -1).all() and np.isneginf(s).all()
    elif case == "blocked_padded_probe_slots":
        assert (i[2] == -1).all()
    elif metric is MetricType.L2:  # the copies tie at -0.0, scan order
        assert i[0, :5].tolist() == ids[copies].tolist()
        assert np.signbit(s[0, :5]).all() and (s[0, :5] == 0).all()


@pytest.mark.parametrize("s,b,t,r", [
    (16, 8, 256, 16), (64, 8, 256, 64), (64, 5, 256, 64), (16, 64, 96, 100),
])
def test_scan_order_selection_orders_signed_zeros_and_infinities(s, b, t, r):
    """The selection orders scores as `lax.top_k` does, by their bits:
    inf first, 0.0 before -0.0, -inf last, and of equal keys the earlier
    scan position first, in levels (the first two shapes; b=5 is no
    multiple of the 8-row tile) and by one `top_k` (t=96 is no whole
    block). Eight values only: every level meets ties."""
    import jax
    import jax.numpy as jnp

    from vearch_tpu.ops import ivf as ivf_ops
    from vearch_tpu.ops import perf_model

    values = np.array([0.0, -0.0, np.inf, -np.inf, 1.5, -1.5, 1e-45,
                       -1e-45], np.float32)
    rng = np.random.default_rng(s * b + t)
    scores = values[rng.integers(0, len(values), (s, b, t))]
    assert perf_model.probe_select_blocked(r, s * t, t) == (t % 128 == 0)
    key = jax.jit(ivf_ops._ascending_key)(jnp.asarray(scores))
    top, pos = map(np.asarray, jax.jit(
        ivf_ops._scan_order_topk, static_argnums=1)(key, r))
    row = scores.transpose(1, 0, 2).reshape(b, s * t)
    order = np.argsort(-_total_order_key(row).astype(np.int64), axis=1,
                       kind="stable")[:, :r]
    np.testing.assert_array_equal(pos, order)
    np.testing.assert_array_equal(
        np.asarray(ivf_ops._ascending_scores(jnp.asarray(top))).view(
            np.int32),
        np.take_along_axis(row, order, axis=1).view(np.int32))


def _plain_fold(best_s, best_i, scores, ids):
    """The running top list after one block, in plain numpy: a stable
    descending order over [best | block], the best r kept."""
    s_cat = np.concatenate([best_s, scores], axis=1)
    i_cat = np.concatenate([best_i, ids], axis=1)
    order = np.argsort(-s_cat, axis=1, kind="stable")[:, :best_s.shape[1]]
    return (np.take_along_axis(s_cat, order, axis=1),
            np.take_along_axis(i_cat, order, axis=1))


@pytest.mark.parametrize("case", [
    "r256_c2048", "small", "ties_in_block", "ties_across", "neg_inf"])
def test_fold_is_a_stable_descending_order(case):
    """`_fold_topk`, one scan step's fold, against a stable descending
    argsort of [best | block]: higher score first, of equal scores the
    running list before the block and a lower slot before a higher one,
    `-inf` (masked slots, `-1` ids) last. The running list is what a
    fold hands on: sorted, and at the scan's start all `-inf`."""
    import jax.numpy as jnp

    from vearch_tpu.ops import ivf as ivf_ops

    rng = np.random.default_rng(37)
    b, r, c = (4, 256, 2048) if case == "r256_c2048" else (3, 4, 6)
    n_ids = b * (r + c)
    ids = rng.permutation(n_ids).astype(np.int32).reshape(b, r + c)
    if case in ("r256_c2048", "ties_in_block", "ties_across"):
        # few distinct values (none of them zero): ties everywhere
        s = (rng.integers(-6, 6, (b, r + c)) + 0.5).astype(np.float32)
    else:
        s = rng.standard_normal((b, r + c)).astype(np.float32)
    if case == "ties_in_block":
        s[:, :r] = rng.standard_normal((b, r)) * 100
    if case == "ties_across":
        s[:, r:r + 2] = s[:, 1:3]  # the block repeats the list's scores
    if case in ("r256_c2048", "neg_inf"):
        masked = rng.random((b, r + c)) < 0.4
        s[masked], ids[masked] = -np.inf, -1
    if case == "neg_inf":
        s[0, :r], ids[0, :r] = -np.inf, -1  # the scan's first step
    order = np.argsort(-s[:, :r], axis=1, kind="stable")
    best_s = np.take_along_axis(s[:, :r], order, axis=1)
    best_i = np.take_along_axis(ids[:, :r], order, axis=1)
    block_s, block_i = s[:, r:], ids[:, r:]
    got_s, got_i = ivf_ops._fold_topk(
        (jnp.asarray(best_s), jnp.asarray(best_i)),
        jnp.asarray(block_s), jnp.asarray(block_i))
    want_s, want_i = _plain_fold(best_s, best_i, block_s, block_i)
    assert want_i.shape == (b, r)
    np.testing.assert_array_equal(np.asarray(got_s), want_s)
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    if case in ("r256_c2048", "neg_inf"):
        assert (np.asarray(got_i)[np.isneginf(want_s)] == -1).all()


def test_fold_orders_signed_zeros_and_infinities_as_top_k():
    """The fold orders floats as `lax.top_k` does, by their bits: 0.0
    before -0.0, inf first, -inf last; and its scores come back bit for
    bit."""
    import jax
    import jax.numpy as jnp

    from vearch_tpu.ops import ivf as ivf_ops

    values = np.array([0.0, -0.0, np.inf, -np.inf, 1.5, -1.5, 1e-45,
                       -1e-45], np.float32)
    rng = np.random.default_rng(7)
    s = values[rng.integers(0, len(values), (6, 40))]
    ids = rng.permutation(6 * 40).astype(np.int32).reshape(6, 40)
    r = 12
    top_s, pos = jax.lax.top_k(jnp.asarray(s), r)
    want_i = np.take_along_axis(ids, np.asarray(pos), axis=1)
    got_s, got_i = ivf_ops._fold_topk(
        (jnp.asarray(s[:, :r]), jnp.asarray(ids[:, :r])),
        jnp.asarray(s[:, r:]), jnp.asarray(ids[:, r:]))
    np.testing.assert_array_equal(np.asarray(got_i), want_i)
    assert (np.asarray(got_s).view(np.int32)
            == np.asarray(top_s).view(np.int32)).all()


# -- the slot-major mask's cache and the guarantees behind it -----------------


def _flat_engine(rng, rows=3000, nlist=16):
    """An IVFFLAT engine with a scalar column, probing EVERY list: its
    answers are exact, so brute force over the rows a mask lets through
    is what each search must return."""
    schema = TableSchema(
        name="flatmask",
        fields=[
            FieldSchema("price", DataType.FLOAT),
            FieldSchema("emb", DataType.VECTOR, dimension=D,
                        index=IndexParams("IVFFLAT", MetricType.L2, {
                            "ncentroids": nlist, "nprobe": nlist,
                            "training_threshold": 1000})),
        ],
    )
    eng = Engine(schema)
    vecs = clustered_data(rng, n=rows)
    price = (np.arange(rows) % 50).astype(np.float32)
    eng.upsert([{"_id": f"d{i}", "emb": vecs[i], "price": float(price[i])}
                for i in range(rows)])
    eng.wait_for_index()
    eng.build_index()
    eng.search(SearchRequest(vectors={"emb": vecs[:1]}, k=1))  # publishes
    return eng, vecs, price


def _keys(res):
    return [[it.key for it in r.items] for r in res]


def _brute_keys(vecs, queries, allowed, k):
    rows = np.flatnonzero(allowed)
    d = ((queries[:, None].astype(np.float64)
          - vecs[rows][None].astype(np.float64)) ** 2).sum(-1)
    return [[f"d{rows[j]}" for j in np.argsort(row, kind="stable")[:k]]
            for row in d]


def _mask_counts(eng):
    info = eng.indexes["emb"].ivf_info()
    return info["mask_builds"], info["mask_hits"], info["publishes"]


def _price_range(lo, hi):
    return {"operator": "AND", "conditions": [
        {"field": "price", "operator": ">=", "value": lo},
        {"field": "price", "operator": "<", "value": hi}]}


def test_same_mask_twice_is_one_build_and_one_hit(rng):
    eng, vecs, _ = _flat_engine(rng)
    q = vecs[:8]
    eng.search(SearchRequest(vectors={"emb": q}, k=5))
    builds, hits, publishes = _mask_counts(eng)
    assert builds >= 1
    first = _keys(eng.search(SearchRequest(vectors={"emb": q}, k=5)))
    again = _keys(eng.search(SearchRequest(vectors={"emb": q}, k=5)))
    # the engine handed back the same alive mask: found again, twice
    assert _mask_counts(eng) == (builds, hits + 2, publishes)
    assert first == again == _brute_keys(vecs, q, np.ones(len(vecs), bool), 5)
    idx = eng.indexes["emb"]
    src, n, ok = idx._mask_entry
    assert src is eng._device_alive_mask(eng.table.doc_count)
    assert ok.shape == (idx.nlist, idx._cap) and ok.dtype == np.bool_
    assert int(np.asarray(ok).sum()) == n == len(vecs)


def test_a_deleted_row_is_gone_from_the_very_next_search(rng):
    eng, vecs, _ = _flat_engine(rng)
    q = vecs[40:44]
    assert [r[0] for r in _keys(eng.search(
        SearchRequest(vectors={"emb": q}, k=3)))] == [
            "d40", "d41", "d42", "d43"]
    builds, hits, publishes = _mask_counts(eng)
    eng.delete(["d41", "d43"])
    alive = np.ones(len(vecs), bool)
    alive[[41, 43]] = False
    got = _keys(eng.search(SearchRequest(vectors={"emb": q}, k=3)))
    assert got == _brute_keys(vecs, q, alive, 3)
    # a new alive mask, the same table: one more mask, no publish
    assert _mask_counts(eng) == (builds + 1, hits, publishes)


def test_an_appended_row_is_found_under_a_new_tables_mask(rng):
    eng, vecs, _ = _flat_engine(rng)
    eng.search(SearchRequest(vectors={"emb": vecs[:2]}, k=3))
    builds, hits, publishes = _mask_counts(eng)
    old = eng.indexes["emb"]._mask_entry
    new = (vecs[7] + 9.0).astype(np.float32)
    eng.upsert([{"_id": "fresh", "emb": new, "price": 1.0}])
    res = eng.search(SearchRequest(vectors={"emb": new[None]}, k=3))
    assert res[0].items[0].key == "fresh"
    # the publish dropped the old table's mask; the new one's was built
    assert _mask_counts(eng) == (builds + 1, hits, publishes + 1)
    idx = eng.indexes["emb"]
    assert idx._mask_entry is not old and idx._mask_entry[1] == len(vecs) + 1
    assert idx._slot_of.shape == (len(vecs) + 1,)
    flat = np.asarray(idx._bucket_ids).reshape(-1)
    assert (flat[idx._slot_of] == np.arange(len(vecs) + 1)).all()


@pytest.mark.parametrize("lo,hi", [(10.0, 40.0), (7.0, 8.0)])
def test_filtered_search_is_brute_force_fresh_and_repeated(rng, lo, hi):
    """60 % and 2 % of the rows pass. A fresh filter is a new host mask:
    one build; the same filter again is the engine's own cached array:
    a hit. Deleted rows stay out of both."""
    eng, vecs, price = _flat_engine(rng)
    eng.delete(["d10", "d57"])
    allowed = (price >= lo) & (price < hi)
    allowed[[10, 57]] = False
    q = vecs[[10, 57, 300, 301, 302]]
    want = _brute_keys(vecs, q, allowed, 10)
    req = lambda: SearchRequest(vectors={"emb": q}, k=10,  # noqa: E731
                                filters=_price_range(lo, hi))
    builds, hits, _ = _mask_counts(eng)
    assert _keys(eng.search(req())) == want
    assert _mask_counts(eng)[:2] == (builds + 1, hits)
    assert _keys(eng.search(req())) == want
    assert _mask_counts(eng)[:2] == (builds + 1, hits + 1)
    # another filter between two uses of one: ONE entry, so a rebuild
    eng.search(SearchRequest(vectors={"emb": q}, k=10,
                             filters=_price_range(0.0, 5.0)))
    assert _keys(eng.search(req())) == want
    assert _mask_counts(eng)[:2] == (builds + 3, hits + 1)


@pytest.mark.parametrize("mask", ["host", "device", "host_short"])
def test_a_row_absorbed_after_the_masks_n_is_never_served(rng, mask):
    """The engine takes its mask at `n` rows, then lets the index absorb
    what a concurrent writer added since: the table may hold rows the
    mask has never heard of. They stay masked, as `to_device_mask`'s
    padding kept them."""
    import jax.numpy as jnp

    eng, vecs, _ = _flat_engine(rng)
    n = eng.table.doc_count
    valid = {"host": np.ones(n, bool), "device": jnp.ones(n, bool),
             "host_short": np.ones(n - 5, bool)}[mask]
    late = (vecs[3] + 7.0).astype(np.float32)
    eng.upsert([{"_id": "late", "emb": late, "price": 0.0}])
    idx = eng.indexes["emb"]
    idx.absorb(eng.vector_stores["emb"].count)
    _, ids = idx.search(np.stack([late, vecs[n - 1]]), 5, valid)
    assert idx._slot_of.shape == (n + 1,)  # the table holds the row
    assert n not in ids.tolist()[0] and (ids >= 0).all()
    if mask == "host_short":  # rows past the mask's own end too
        assert (ids < n - 5).all()
    else:
        assert ids[1, 0] == n - 1
    # under a mask that covers it, it is the nearest
    _, ids = idx.search(late[None], 5, None)
    assert ids[0, 0] == n


@pytest.mark.parametrize("rows", [2500, 3000, 700])
def test_bulk_absorb_in_pieces_builds_the_same_index(rng, monkeypatch, rows):
    """An index build's absorb sends its rows to the device in pieces of
    `BULK_ROWS` (one compiled shape whatever the partition's size, the
    last piece zero-padded): codes, assignments and the int8 mirror are
    what one call over all rows gives. 2500 rows = two pieces and a
    padded third, 3000 = three whole pieces, 700 = under one piece."""
    from vearch_tpu.engine.raw_vector import RawVectorStore
    from vearch_tpu.index import ivf
    from vearch_tpu.index.ivf import IVFPQIndex

    data = rng.standard_normal((rows, 32)).astype(np.float32)

    def build(bulk_rows):
        monkeypatch.setattr(ivf, "BULK_ROWS", bulk_rows)
        store = RawVectorStore(32)
        store.add(data)
        idx = IVFPQIndex(IndexParams("IVFPQ", MetricType.L2, {
            "ncentroids": 16, "nsubvector": 8, "train_iters": 4}), store)
        idx.train(data[:600])
        idx.absorb(rows)
        return idx

    whole, pieces = build(1 << 20), build(1000)
    np.testing.assert_array_equal(pieces._codes[:rows], whole._codes[:rows])
    np.testing.assert_array_equal(pieces._assign_host[:rows],
                                  whole._assign_host[:rows])
    for a, b in zip(pieces._mirror.flush(), whole._mirror.flush()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert pieces._members == whole._members
