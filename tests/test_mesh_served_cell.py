"""The deployment of benchmark cell `deep10m-mesh4.b64x4-closed`, small,
on the served path: router -> PS -> engine -> ONE mesh program over the
conftest's forced host devices, at 20,000 x 96 with 64-row requests.

Held to the benchmark's own plain reference (`benchmark/data.py`
`ExactReference`) under the configuration's own limits through
`benchmark/check.py` `compare`, and to the same partition served from
one device (`mesh_serving: off` per request), id for id: before a
write, and again after an acknowledged upsert and delete (the write
check `benchmark/run.py` runs after every window). After the write the
partition still serves from the sharded arrays: the new row arrives as
a tail-append on the shard that owns it, nothing is re-placed, and the
unsharded raw buffer is not touched by the mesh path.
"""

import jax
import numpy as np
import pytest

from benchmark import cells, check, corpus, data, loadgen
from vearch_tpu.ops import perf_model

CELL = "deep10m-mesh4.b64x4-closed"
ROWS, B, SEED = 20_000, 64, 2_718_281_829
NEW_ID = "bench_new"


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
        self.base, self.queries, _ = data.make_data(self.cfg, SEED % 2 ** 32,
                                                    ROWS)
        self.ref = data.ExactReference(self.base, self.cfg["metric"])
        self.truth = self.ref.topk(self.queries, self.k)
        self.cluster = StandaloneCluster(data_dir=str(tmp), n_ps=1).start()
        self.client = VearchClient(self.cluster.router_addr)
        self.client.create_database(corpus.DB)
        self.client.create_space(corpus.DB,
                                 corpus.space_config(self.cfg, ROWS))
        self.space = self.cfg["space"]["name"]
        col = data.scalar_column(self.cfg["scalar_columns"][0], ROWS)
        for lo in range(0, ROWS, 5000):
            self.client.upsert(corpus.DB, self.space, [
                {"_id": f"doc{i}", "emb": self.base[i], "price": float(col[i])}
                for i in range(lo, lo + 5000)])
        self.ps = self.cluster.ps_nodes[0]
        self.engine = next(iter(self.ps.engines.values()))
        self.engine.wait_for_index(timeout=600)
        self.store = self.engine.vector_stores["emb"]

    def search(self, queries, **index_params):
        out = self.client.search(
            corpus.DB, self.space,
            vectors=[{"field": "emb", "feature": queries}], limit=self.k,
            fields=[], profile=True, cache=False,
            index_params={**self.cfg["search"]["index_params"],
                          **index_params})
        (part,) = out["profile"]["partitions"].values()
        return out["documents"], part

    def placement(self) -> dict:
        from vearch_tpu.cluster import rpc

        (part,) = rpc.call(self.ps.addr, "GET",
                           "/ps/stats")["partitions"].values()
        return part["mesh"]["fields"]["emb"]


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    w = World(tmp_path_factory.mktemp("mesh_cell"))
    yield w
    w.cluster.stop()


def serves_the_cell(w: World) -> None:
    """(a) the mesh program served, (b) its answers pass the cell's own
    comparison, (c) they equal the single-device twin's id for id."""
    rec = loadgen.Recorder(B, w.k)
    for lo in range(0, w.queries.shape[0], B):
        q_idx = np.arange(lo, lo + B)
        docs, part = w.search(w.queries[q_idx])
        assert part["dispatches"]["tags"] == [
            w.cfg["serving"]["dispatch_tag"]], part["dispatches"]
        assert part["mesh"]["devices"] == len(jax.devices()) > 1
        rec.add(0.0, 0.0, 0.0, q_idx, docs, None, None)
        one, part = w.search(w.queries[q_idx], mesh_serving="off")
        assert part["dispatches"]["tags"] == ["fused_scan_rerank"]
        assert [[h["_id"] for h in row] for row in one] == \
            [[h["_id"] for h in row] for row in docs]
    got = rec.arrays()
    checks, _ = check.compare(w.cfg, w.ref, w.queries, w.truth,
                              got["q_idx"], got["ids"], got["scores"])
    assert all(check.passed(c) for c in checks.values()), checks
    assert checks["answers_compared"]["value"] == w.queries.shape[0]
    assert checks["recall_at_10"]["limit"] == 0.95
    assert checks["score_err"]["limit"] == 3e-06


def test_mesh_partition_serves_the_cell_like_reference_and_twin(world):
    docs, part = world.search(world.queries[:B])
    assert part["dispatches"]["tags"] == ["sharded_fused_scan_rerank"]
    placed = world.placement()
    assert placed["data_shards"] == len(jax.devices())
    assert placed["raw_placement"]["rebuilds"] == 1
    # nothing but the mesh has asked for the raw store so far: no
    # unsharded copy exists on any device
    assert world.store._device is None
    serves_the_cell(world)


def test_the_dispatch_span_carries_launch_and_rows_and_place_its_bytes(world):
    from vearch_tpu.cluster import tracing

    docs, _ = world.search(world.queries[:B - 4])  # 60 rows in a 64 bucket
    spans = [s for s in tracing.snapshot()
             if s.name in ("kernel.sharded_fused_scan_rerank", "mesh.place")]
    kernel = [s for s in spans if s.name.startswith("kernel.")][-1]
    place = [s for s in spans if s.name == "mesh.place"][-1]
    assert kernel.trace_id == place.trace_id
    assert kernel.tags["rows"] == B - 4 and kernel.tags["bucket_rows"] == B
    assert 0 < kernel.tags["launch_us"] * 1e3 <= kernel.t1_ns - kernel.t0_ns
    # the selection of a SHARD's rows: at this size the plain row
    index = world.engine.indexes["emb"]
    shard_rows = index._mirror._sh_cache.capacity(
        index._serving_mesh(None), index.indexed_count) // len(jax.devices())
    assert kernel.tags["select_width"] == shard_rows \
        == perf_model.select_width(
            world.cfg["search"]["index_params"]["rerank"], shard_rows)
    # at least this request's own query batch went up during the phase
    assert place.tags["bytes"] >= B * world.cfg["dimension"] * 4
    assert place.t1_ns <= kernel.t0_ns + 1_000_000


def test_write_check_on_a_mesh_partition_keeps_it_sharded(world):
    """benchmark/run.py `write_read_delete`, step by step, with the
    placement read between the steps."""
    w = world
    w.search(w.queries[:B])  # placed, whichever test ran before
    before = w.placement()
    # rows in the one-device buffer: the twin's copy if an earlier test
    # asked for it, none otherwise
    on_one_device = w.store._device_rows
    vec = (w.queries[1] + 40.0).astype(np.float32)
    out = w.client.upsert(corpus.DB, w.space,
                          [{"_id": NEW_ID, "emb": vec, "price": 1.0}])
    assert out["total"] == 1
    got = w.client.query(corpus.DB, w.space, document_ids=[NEW_ID],
                         vector_value=True)
    assert len(got) == 1 and np.allclose(got[0]["emb"], vec)
    docs, part = w.search(vec)
    assert part["dispatches"]["tags"] == ["sharded_fused_scan_rerank"]
    assert docs[0][0]["_id"] == NEW_ID
    after = w.placement()
    for cache in ("raw_placement", "mirror_placement"):
        assert after[cache]["rebuilds"] == before[cache]["rebuilds"], cache
        assert after[cache]["appends"] == before[cache]["appends"] + 1, cache
    # one 128-row (raw) and one 512-row (mirror) aligned window went up,
    # not the store
    d = w.cfg["dimension"]
    assert (after["raw_placement"]["h2d_bytes"]
            - before["raw_placement"]["h2d_bytes"]) == 128 * (d * 4 + 4)
    # the mesh path did not refresh the one-device buffer with the row
    assert w.store.count == ROWS + 1
    assert w.store._device_rows == on_one_device <= ROWS
    assert w.client.delete(corpus.DB, w.space, document_ids=[NEW_ID]) == 1
    assert w.client.query(corpus.DB, w.space, document_ids=[NEW_ID]) == []
    docs, part = w.search(vec)
    assert part["dispatches"]["tags"] == ["sharded_fused_scan_rerank"]
    assert all(h["_id"] != NEW_ID for h in docs[0])
    assert w.placement()["raw_placement"] == after["raw_placement"]


def test_mesh_partition_serves_the_cell_after_the_write(world):
    serves_the_cell(world)
    assert world.placement()["raw_placement"]["rebuilds"] == 1
