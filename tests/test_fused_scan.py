"""Fused scan+rerank hot path (r4 review next-1).

Proves, on the CPU backend (counts and equality, not speed):
- RESULT EQUALITY: the fused one-program path returns exactly what the
  two programs it fuses return when called one after the other
  (`int8_scan_candidates` / `int4_scan_candidates`, then
  `exact_rerank`), for int8 and int4 mirrors, L2 and cosine, with and
  without filters;
- DISPATCH REDUCTION: the ledger records ONE device-program launch per
  search where the two-step path a disk store takes records two (each
  dispatch pays launch scheduling; what that costs on the chip is a
  chip run's to say).
"""

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
from vearch_tpu.ops import ivf as ivf_ops

D = 32
N = 3000


def _engine(metric=MetricType.L2, storage="int8"):
    params = {
        "ncentroids": 16, "nsubvector": 8, "train_iters": 4,
        "training_threshold": 256, "mirror_dtype": storage,
        # these tests assert the single-device ledger; under the
        # forced-8-device conftest mesh auto would reroute every
        # full-mode search through the mesh program
        "mesh_serving": "off",
    }
    schema = TableSchema("t", [
        FieldSchema("group", DataType.INT),
        FieldSchema("emb", DataType.VECTOR, dimension=D,
                    index=IndexParams("IVFPQ", metric, params)),
    ])
    eng = Engine(schema)
    rng = np.random.default_rng(21)
    vecs = rng.standard_normal((N, D), dtype=np.float32)
    eng.upsert([
        {"_id": f"d{i:04d}", "group": i % 4, "emb": vecs[i]}
        for i in range(N)
    ])
    eng.build_index()
    eng.wait_for_index()
    return eng, vecs


@pytest.fixture(scope="module")
def l2_engine():
    return _engine(MetricType.L2)


def _run(eng, vecs, filters=None):
    """The engine's search of the first 8 rows: (rows of (key, score),
    dispatch ledger)."""
    ledger: list = []
    ivf_ops.set_dispatch_ledger(ledger)
    try:
        req = SearchRequest(
            vectors={"emb": vecs[:8]}, k=10, filters=filters,
            include_fields=[], index_params={"scan_mode": "full"},
        )
        res = eng.search(req)
    finally:
        ivf_ops.set_dispatch_ledger(None)
    rows = [[(it.key, round(it.score, 4)) for it in r.items] for r in res]
    return rows, ledger


def _fused_and_two_step(eng, vecs, valid_mask=None, k=10):
    """(scores, ids) of the index's own search and of the reference:
    the scan, then the exact rerank, as two programs of ops/ivf.py."""
    import jax.numpy as jnp

    from vearch_tpu.ops.distance import to_device_mask

    idx = eng.indexes["emb"]
    fused = idx.search(vecs[:8], k, valid_mask, {"scan_mode": "full"})
    q = jnp.asarray(idx._maybe_normalize(vecs[:8]))
    approx8, scale, vsq = idx._mirror.flush()
    valid = to_device_mask(valid_mask, idx.indexed_count, approx8.shape[0])
    scan = (ivf_ops.int8_scan_candidates if idx.mirror_storage == "int8"
            else ivf_ops.int4_scan_candidates)
    scan_metric = (MetricType.INNER_PRODUCT
                   if idx.metric is MetricType.COSINE else idx.metric)
    _, cand = scan(q, approx8, scale, vsq, valid,
                   idx._rerank_depth(k, None), scan_metric)
    base, base_sqnorm, _ = idx.store.device_buffer()
    plain = ivf_ops.exact_rerank(q.astype(base.dtype), cand, base,
                                 base_sqnorm, k, idx.metric)
    return fused, tuple(np.asarray(a) for a in plain)


def _assert_same(fused, plain):
    np.testing.assert_array_equal(fused[1], plain[1])
    np.testing.assert_array_equal(fused[0], plain[0])


def test_fused_equals_unfused_and_halves_dispatches(l2_engine, tmp_path):
    eng, vecs = l2_engine
    _assert_same(*_fused_and_two_step(eng, vecs))
    _, fused_ledger = _run(eng, vecs)
    assert fused_ledger == ["fused_scan_rerank"]
    # a disk store cannot hand a program its raw rows: two dispatches
    from vearch_tpu.engine.disk_vector import DiskRawVectorStore
    from vearch_tpu.index.registry import create_index

    store = DiskRawVectorStore(D, str(tmp_path / "store"))
    store.add(vecs)
    idx = create_index(eng.schema.field("emb").index, store)
    idx.train(vecs)
    idx.absorb(store.count)
    plain_ledger: list = []
    ivf_ops.set_dispatch_ledger(plain_ledger)
    try:
        _, ids = idx.search(vecs[:8], 10, None, {"scan_mode": "full"})
    finally:
        ivf_ops.set_dispatch_ledger(None)
    assert plain_ledger == ["scan", "rerank"]
    assert list(ids[:, 0]) == list(range(8))


def test_fused_respects_filters(l2_engine):
    eng, vecs = l2_engine
    filt = {"operator": "AND",
            "conditions": [{"field": "group", "operator": "=", "value": 2}]}
    _assert_same(*_fused_and_two_step(
        eng, vecs, valid_mask=np.arange(N) % 4 == 2))
    fused_rows, ledger = _run(eng, vecs, filters=filt)
    assert ledger == ["fused_scan_rerank"]
    for rows in fused_rows:
        for key, _ in rows:
            assert int(key[1:]) % 4 == 2


def test_fused_cosine_metric():
    eng, vecs = _engine(MetricType.COSINE)
    _assert_same(*_fused_and_two_step(eng, vecs))
    fused_rows, ledger = _run(eng, vecs)
    assert ledger == ["fused_scan_rerank"]
    # cosine scores live in [-1, 1]
    assert all(-1.001 <= s <= 1.001 for rows in fused_rows for _, s in rows)


def test_fused_int4_mirror():
    eng, vecs = _engine(MetricType.L2, storage="int4")
    assert eng.indexes["emb"].mirror_storage == "int4"
    _assert_same(*_fused_and_two_step(eng, vecs))
    _, ledger = _run(eng, vecs)
    assert ledger == ["fused_scan_rerank"]
