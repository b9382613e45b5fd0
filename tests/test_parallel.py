"""Multi-chip sharding tests on the 8-device virtual CPU mesh
(conftest.py forces xla_force_host_platform_device_count=8)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vearch_tpu.engine.types import MetricType
from vearch_tpu.ops.distance import brute_force_search
from vearch_tpu.parallel import mesh as mesh_lib
from vearch_tpu.parallel.sharded import (
    ShardedFlatSearcher,
    sharded_int8_search,
    train_kmeans_sharded,
)


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_sharded_flat_matches_single_device(rng):
    base = rng.standard_normal((1000, 32)).astype(np.float32)
    queries = rng.standard_normal((16, 32)).astype(np.float32)
    mesh = mesh_lib.make_mesh(8)
    searcher = ShardedFlatSearcher(mesh, base, store_dtype="float32")
    s_sh, i_sh = searcher.search(queries, 10)

    s_1, i_1 = brute_force_search(
        jnp.asarray(queries), jnp.asarray(base), None, 10, MetricType.L2
    )
    np.testing.assert_array_equal(i_sh, np.asarray(i_1))
    np.testing.assert_allclose(s_sh, np.asarray(s_1), rtol=1e-4, atol=1e-4)


def test_sharded_flat_2d_mesh_query_axis(rng):
    base = rng.standard_normal((512, 16)).astype(np.float32)
    queries = rng.standard_normal((8, 16)).astype(np.float32)
    mesh = mesh_lib.make_mesh(8, data_axis=4, query_axis=2)
    searcher = ShardedFlatSearcher(mesh, base, store_dtype="float32")
    s_sh, i_sh = searcher.search(queries, 5)
    s_1, i_1 = brute_force_search(
        jnp.asarray(queries), jnp.asarray(base), None, 5, MetricType.L2
    )
    np.testing.assert_array_equal(i_sh, np.asarray(i_1))


def test_sharded_flat_n_not_divisible(rng):
    # 1003 rows over 8 shards: padding rows must never surface
    base = rng.standard_normal((1003, 16)).astype(np.float32)
    queries = base[:4]
    mesh = mesh_lib.make_mesh(8)
    searcher = ShardedFlatSearcher(mesh, base, store_dtype="float32")
    s_sh, i_sh = searcher.search(queries, 3)
    assert (i_sh[:, 0] == np.arange(4)).all()
    assert (i_sh < 1003).all()


def test_sharded_kmeans_matches_quality(rng):
    centers = rng.standard_normal((8, 16)).astype(np.float32) * 4
    x = np.concatenate(
        [c + 0.1 * rng.standard_normal((80, 16)).astype(np.float32)
         for c in centers]
    )
    mesh = mesh_lib.make_mesh(8)
    cents = np.asarray(train_kmeans_sharded(mesh, x, k=8, iters=12))
    d = np.linalg.norm(centers[:, None] - cents[None], axis=-1)
    assert (d.min(axis=1) < 0.5).all()


def test_engine_sharded_flat_index(rng):
    """FLAT {"sharded": true} through the full Engine API on the 8-device
    mesh: results must match the single-device FLAT engine."""
    from vearch_tpu.engine.engine import Engine, SearchRequest
    from vearch_tpu.engine.types import (
        DataType, FieldSchema, IndexParams, MetricType, TableSchema,
    )

    def build(params):
        schema = TableSchema("sf", [FieldSchema(
            "v", DataType.VECTOR, dimension=16,
            index=IndexParams("FLAT", MetricType.L2, params))])
        return Engine(schema)

    vecs = rng.standard_normal((500, 16)).astype(np.float32)
    docs = [{"_id": f"d{i}", "v": vecs[i]} for i in range(500)]
    eng_s = build({"sharded": True, "store_dtype": "float32"})
    eng_1 = build({"store_dtype": "float32"})
    eng_s.upsert(docs)
    eng_1.upsert(docs)
    req = SearchRequest(vectors={"v": vecs[:6]}, k=5)
    res_s = eng_s.search(req)
    res_1 = eng_1.search(req)
    for rs, r1 in zip(res_s, res_1):
        assert [it.key for it in rs.items] == [it.key for it in r1.items]
        for a, b in zip(rs.items, r1.items):
            assert abs(a.score - b.score) < 1e-3

    # deletes are honored on the mesh path
    eng_s.delete(["d3"])
    res = eng_s.search(SearchRequest(vectors={"v": vecs[3:4]}, k=5))
    assert all(it.key != "d3" for it in res[0].items)

    # realtime rows appear after re-place
    new = rng.standard_normal(16).astype(np.float32) + 6.0
    eng_s.upsert([{"_id": "new", "v": new}])
    res = eng_s.search(SearchRequest(vectors={"v": new}, k=1))
    assert res[0].items[0].key == "new"


def test_sharded_int8_search(rng):
    base = rng.standard_normal((800, 32)).astype(np.float32)
    queries = base[:6]
    mesh = mesh_lib.make_mesh(8)
    scale = np.maximum(np.abs(base).max(axis=1) / 127.0, 1e-12).astype(np.float32)
    q8 = np.clip(np.rint(base / scale[:, None]), -127, 127).astype(np.int8)
    deq = q8.astype(np.float32) * scale[:, None]
    vsq = np.sum(deq * deq, axis=1).astype(np.float32)

    a8, n = mesh_lib.shard_rows(mesh, q8)
    sc, _ = mesh_lib.shard_rows(mesh, scale)
    vs, _ = mesh_lib.shard_rows(mesh, vsq)
    valid, _ = mesh_lib.shard_rows(mesh, np.arange(a8.shape[0]) < n)
    qd, b = mesh_lib.shard_queries(mesh, queries)
    s, i = sharded_int8_search(mesh, a8, sc, vs, valid, qd, 5)
    i = np.asarray(i)[:b]
    # int8 quantization is fine enough for self-match top-1
    assert (i[:, 0] == np.arange(6)).all()


def test_ivfpq_mesh_serving_matches_single_device(rng):
    """Engine-level mesh-spanning IVFPQ partition: mesh_serving "on"
    row-shards the int8 mirror + rerank buffer over all 8 CPU devices;
    results must match the single-device path."""
    from vearch_tpu.engine.engine import Engine, SearchRequest
    from vearch_tpu.engine.types import (
        DataType, FieldSchema, IndexParams, MetricType, TableSchema,
    )

    n, d = 6000, 32
    base = rng.standard_normal((n, d)).astype(np.float32)

    def make_engine(mesh_serving):
        schema = TableSchema("m", [
            FieldSchema("v", DataType.VECTOR, dimension=d,
                        index=IndexParams("IVFPQ", MetricType.L2, {
                            "ncentroids": 32, "nsubvector": 8,
                            "train_iters": 4, "training_threshold": 2 * n,
                            "mesh_serving": mesh_serving,
                        })),
        ])
        eng = Engine(schema)
        step = 2000
        for i in range(0, n, step):
            eng.upsert([{"_id": f"d{j}", "v": base[j]}
                        for j in range(i, i + step)])
        eng.build_index()
        return eng

    e1 = make_engine("off")
    e8 = make_engine("on")
    q = base[rng.choice(n, 16, replace=False)]
    req = lambda: SearchRequest(vectors={"v": q}, k=5, include_fields=[],
                                index_params={"rerank": 64})
    r1 = e1.search(req())
    r8 = e8.search(req())
    for a, b in zip(r1, r8):
        assert [i.key for i in a.items] == [i.key for i in b.items]
        for x, y in zip(a.items, b.items):
            assert abs(x.score - y.score) < 1e-2, (x.score, y.score)
    # deletes respected on the mesh path
    e8.delete([r8[0].items[0].key])
    r8b = e8.search(req())
    assert r8b[0].items[0].key == r8[0].items[1].key


def test_mesh_callables_are_cached():
    """Repeated mesh searches must reuse one jitted program (re-creating
    the shard_map closure per call would retrace every search)."""
    from vearch_tpu.parallel import sharded

    before = sharded._flat_search_fn.cache_info().currsize
    mesh = mesh_lib.default_mesh()
    f1 = sharded._flat_search_fn(mesh, 5, MetricType.L2)
    f2 = sharded._flat_search_fn(mesh, 5, MetricType.L2)
    assert f1 is f2
    assert sharded._flat_search_fn.cache_info().currsize <= before + 1
