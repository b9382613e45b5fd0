"""Fused scan+rerank hot path (r4 review next-1).

Proves, on the CPU backend (counts and equality, not speed):
- RESULT EQUALITY: the fused one-program path returns exactly the
  two-dispatch path's (scores, ids) for int8 and int4 mirrors, L2 and
  cosine, with and without filters;
- DISPATCH REDUCTION: the ledger records ONE device-program launch per
  search where the unfused path records two (each dispatch pays launch
  scheduling; what that costs on the chip is a chip run's to say).
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
        "training_threshold": 256, "mirror_storage": storage,
        # these tests assert the single-device fused/unfused ledgers;
        # under the forced-8-device conftest mesh auto would reroute
        # every full-mode search through the mesh program
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


def _run(eng, vecs, fused: bool, filters=None, storage_params=None):
    ledger: list = []
    ivf_ops.set_dispatch_ledger(ledger)
    try:
        req = SearchRequest(
            vectors={"emb": vecs[:8]}, k=10, filters=filters,
            include_fields=[],
            index_params={"fused_rerank": fused,
                          "scan_mode": "full",
                          **(storage_params or {})},
        )
        res = eng.search(req)
    finally:
        ivf_ops.set_dispatch_ledger(None)
    rows = [[(it.key, round(it.score, 4)) for it in r.items] for r in res]
    return rows, ledger


def test_fused_equals_unfused_and_halves_dispatches(l2_engine):
    eng, vecs = l2_engine
    fused_rows, fused_ledger = _run(eng, vecs, fused=True)
    plain_rows, plain_ledger = _run(eng, vecs, fused=False)
    assert fused_rows == plain_rows
    assert fused_ledger == ["fused_scan_rerank"]
    assert plain_ledger == ["scan", "rerank"]


def test_fused_respects_filters(l2_engine):
    eng, vecs = l2_engine
    filt = {"operator": "AND",
            "conditions": [{"field": "group", "operator": "=", "value": 2}]}
    fused_rows, ledger = _run(eng, vecs, fused=True, filters=filt)
    plain_rows, _ = _run(eng, vecs, fused=False, filters=filt)
    assert fused_rows == plain_rows
    assert ledger == ["fused_scan_rerank"]
    for rows in fused_rows:
        for key, _ in rows:
            assert int(key[1:]) % 4 == 2


def test_fused_cosine_metric():
    eng, vecs = _engine(MetricType.COSINE)
    fused_rows, ledger = _run(eng, vecs, fused=True)
    plain_rows, _ = _run(eng, vecs, fused=False)
    assert fused_rows == plain_rows
    assert ledger == ["fused_scan_rerank"]
    # cosine scores live in [-1, 1]
    assert all(-1.001 <= s <= 1.001 for rows in fused_rows for _, s in rows)


def test_fused_int4_mirror():
    eng, vecs = _engine(MetricType.L2, storage="int4")
    fused_rows, ledger = _run(eng, vecs, fused=True)
    plain_rows, _ = _run(eng, vecs, fused=False)
    assert fused_rows == plain_rows
    assert ledger == ["fused_scan_rerank"]


def test_unfused_flag_preserved_for_ab():
    """`fused_rerank: false` stays available as the A/B escape hatch."""
    eng, vecs = _engine(MetricType.L2)
    _, ledger = _run(eng, vecs, fused=False)
    assert ledger == ["scan", "rerank"]
