"""Pod-slice mesh serving: the multi-chip data plane under the forced
8-device CPU mesh (conftest.py).

Gates (ISSUE 7 acceptance criteria):
- sharded search results bit-identical to the single-device path, on a
  fresh build AND through incremental absorb tail-appends;
- deletion-bitmap masking correct across shards;
- mesh dispatch ledgers match DOCUMENTED_DISPATCHES and warmed searches
  compile zero new programs;
- absorb tail-appends per shard (H2D bytes match the window model,
  never a full re-place);
- the per-device HBM footprint model divides sharded state by the
  shard count;
- router -> PS end-to-end with mesh on serves search/upsert/delete
  identically to a mesh-off space.
"""

import threading

import numpy as np
import pytest

from vearch_tpu.engine.engine import Engine, SearchRequest
from vearch_tpu.engine.raw_vector import RawVectorStore
from vearch_tpu.engine.types import (
    DataType, FieldSchema, IndexParams, MetricType, TableSchema,
)
from vearch_tpu.index.flat import FlatIndex
from vearch_tpu.index.ivf import IVFPQIndex
from vearch_tpu.index.sharded_flat import ShardedFlatIndex
from vearch_tpu.ops import ivf as ivf_ops
from vearch_tpu.ops import perf_model
from vearch_tpu.parallel import mesh as mesh_lib

from tests.test_perf_gates import _build, _search

D = 32
N = 3000

MESH_PARAMS = {
    "ncentroids": 16, "nsubvector": 8, "train_iters": 4,
    "training_threshold": 256, "mesh_serving": "on",
}


def _ivfpq_pair(rng, metric=MetricType.L2, storage="int8", n=N,
                mesh_shape=None, d=D, train_rows=2000, nsubvector=8):
    """Same data, same training → one single-device index, one mesh."""
    data = rng.standard_normal((n, d)).astype(np.float32)

    def build(ms):
        params = IndexParams("IVFPQ", metric, {
            "ncentroids": 16, "nsubvector": nsubvector, "train_iters": 4,
            "mirror_dtype": storage, "mesh_serving": ms,
            "mesh_shape": mesh_shape,
        })
        store = RawVectorStore(d)
        store.add(data)
        idx = IVFPQIndex(params, store)
        idx.train(data[:train_rows])
        idx.absorb(n)
        return idx

    return build("off"), build("on"), data


# -- bit-equality with the single-device path --------------------------------


@pytest.mark.parametrize("storage", ["int8", "int4"])
def test_mesh_ivfpq_bit_identical(rng, storage):
    single, mesh, _ = _ivfpq_pair(rng, storage=storage)
    q = rng.standard_normal((4, D)).astype(np.float32)
    ss, si = single.search(q, 10, None)
    ms, mi = mesh.search(q, 10, None)
    assert np.array_equal(si, mi)
    assert np.array_equal(ss, ms)


@pytest.mark.parametrize("storage", ["int8", "int4"])
def test_mesh_two_stage_selection_matches_exact(rng, storage):
    """Every shard hands its own [B, N/shards] scores to the selection
    the single-device scan uses. At 512 blocks a shard, where r=128
    selects in two stages on both (tests/test_index_ivf.py holds that
    selection to `lax.top_k` over the row), the mesh returns what the
    single-device twin returns."""
    from vearch_tpu.ops.ivf import BLOCK

    shards, r = 2, 128
    n = shards * 4 * r * BLOCK
    single, mesh, _ = _ivfpq_pair(rng, storage=storage, n=n,
                                  mesh_shape=f"{shards}x1")
    q = rng.standard_normal((8, D)).astype(np.float32)
    es, ei = single.search(q, 10, None, {"rerank": r})
    bs, bi = mesh.search(q, 10, None, {"rerank": r})
    assert mesh._mirror._sh_cache.capacity(
        mesh._serving_mesh(None), n) == n  # no padding: 512 blocks each
    assert np.array_equal(bi, ei)
    assert np.array_equal(bs, es)


def test_mesh_ivfpq_bit_identical_through_absorb(rng):
    """Incremental tail-appends land the same device state as a full
    place: results stay bit-identical across repeated absorb rounds."""
    single, mesh, _ = _ivfpq_pair(rng)
    q = rng.standard_normal((4, D)).astype(np.float32)
    for _ in range(3):
        more = rng.standard_normal((500, D)).astype(np.float32)
        single.store.add(more)
        mesh.store.add(more)
        n = single.store.count
        single.absorb(n)
        mesh.absorb(n)
        ss, si = single.search(q, 10, None)
        ms, mi = mesh.search(q, 10, None)
        assert np.array_equal(si, mi)
        assert np.array_equal(ss, ms)
    assert mesh._mirror._sh_cache.stats["appends"] >= 1


def test_mesh_deletion_mask_across_shards(rng):
    """Deleted docids on every shard are masked inside the sharded scan
    (masked top-k, not post-filter) — identically to single-device."""
    single, mesh, _ = _ivfpq_pair(rng)
    q = rng.standard_normal((4, D)).astype(np.float32)
    _, base_ids = single.search(q, 20, None)
    # kill the current top hits; they land on different shards
    dead = sorted({int(i) for i in base_ids[:, :8].ravel() if i >= 0})
    mask = np.ones(N, dtype=bool)
    mask[dead] = False
    ss, si = single.search(q, 10, mask)
    ms, mi = mesh.search(q, 10, mask)
    assert np.array_equal(si, mi)
    assert np.array_equal(ss, ms)
    assert not (set(dead) & {int(i) for i in mi.ravel()})


def test_mesh_flat_sharded_matches_flat(rng):
    data = rng.standard_normal((2000, D)).astype(np.float32)
    q = rng.standard_normal((3, D)).astype(np.float32)
    for metric in (MetricType.L2, MetricType.INNER_PRODUCT,
                   MetricType.COSINE):
        def mk(cls, itype):
            store = RawVectorStore(D)
            store.add(data)
            idx = cls(IndexParams(itype, metric, {}), store)
            idx.absorb(2000)
            return idx

        flat = mk(FlatIndex, "FLAT")
        sharded = mk(ShardedFlatIndex, "FLAT_SHARDED")
        fs, fi = flat.search(q, 10, None)
        shs, shi = sharded.search(q, 10, None)
        assert np.array_equal(fi, shi), metric
        if metric is MetricType.COSINE:
            # FLAT scores cosine by sqnorm division, FLAT_SHARDED
            # normalizes rows then takes IP — same ranking, 1-ulp
            # score noise between the two formulations
            assert np.allclose(fs, shs, atol=1e-5)
        else:
            assert np.array_equal(fs, shs), metric


def test_mesh_probe_gate_recall(rng):
    """mesh_nprobe gates the fused program to probed cells: it prunes,
    so exactness is out — but recall against the ungated scan must stay
    high at moderate nprobe."""
    _, mesh, _ = _ivfpq_pair(rng)
    q = rng.standard_normal((8, D)).astype(np.float32)
    _, full_i = mesh.search(q, 10, None)
    _, probed_i = mesh.search(q, 10, None, {"mesh_nprobe": 8})
    overlap = np.mean([
        len(set(full_i[r]) & set(probed_i[r])) / 10
        for r in range(q.shape[0])
    ])
    assert overlap >= 0.7, overlap


# -- query-axis parallelism (ISSUE 16) ---------------------------------------


def test_mesh_query_axis_bit_identical_to_data_only(rng):
    """query_axis=2 serves the IVF path bit-identical to the data×1
    mesh: each query row's scan/rerank math is untouched by which
    query-shard computes it, and the data-axis merge is an exact top-k
    over exact scores — so changing EITHER axis must not move a bit."""
    _, mesh, _ = _ivfpq_pair(rng)
    base = {"scan_mode": "full"}
    for rows in (8, 3):  # 3 exercises query-axis padding (3 -> 4)
        q = rng.standard_normal((rows, D)).astype(np.float32)
        ss, si = mesh.search(q, 10, None, base)  # default data×1 (8x1)
        for shape in ("4x2", "4x1"):
            ms, mi = mesh.search(q, 10, None, dict(base, mesh_shape=shape))
            assert np.array_equal(si, mi), shape
            assert np.array_equal(ss, ms), shape
        # shrinking the data axis further (2x4) reshapes the gathered-
        # candidate rerank gemm — same ids, low-f32-bit score drift.
        # The guarantee under test is query-axis invariance, not
        # arbitrary re-sharding of the data axis.
        ms, mi = mesh.search(q, 10, None, dict(base, mesh_shape="2x4"))
        assert np.array_equal(si, mi)
        assert np.allclose(ss, ms, rtol=1e-5)


def test_mesh_shape_knob_single_parse_point():
    """Every spelling of the knob lands on the same cached Mesh object
    (shard_map program caches key on mesh identity)."""
    assert mesh_lib.mesh_from_shape("4x2") is \
        mesh_lib.make_mesh(8, data_axis=4, query_axis=2)
    assert mesh_lib.mesh_from_shape((4, 2)) is mesh_lib.mesh_from_shape("4x2")
    assert mesh_lib.mesh_from_shape(8) is mesh_lib.default_mesh()
    for alias in (None, "", "auto", "default"):
        assert mesh_lib.mesh_from_shape(alias) is mesh_lib.default_mesh()
    m = mesh_lib.mesh_from_shape("2x4")
    assert (m.shape["data"], m.shape["query"]) == (2, 4)


def test_mesh_query_axis_engine_apply_config(rng):
    """apply_config({"mesh_shape": ...}) fans the knob into live index
    params: the next search re-places onto the new mesh and stays
    bit-identical."""
    eng, vecs = _build("IVFPQ", dict(MESH_PARAMS), n=1200)
    req = {"scan_mode": "full"}
    ledger = _search(eng, vecs, index_params=req)
    assert ledger.tags == perf_model.DOCUMENTED_DISPATCHES["ivfpq_mesh_fused"]
    res0 = eng.search(SearchRequest(
        vectors={"emb": vecs[:8]}, k=10, include_fields=[],
        index_params=req))
    eng.apply_config({"mesh_shape": "4x2"})
    idx = eng.indexes["emb"]
    assert idx._serving_mesh(None).shape["query"] == 2
    res1 = eng.search(SearchRequest(
        vectors={"emb": vecs[:8]}, k=10, include_fields=[],
        index_params=req))
    for r0, r1 in zip(res0, res1):
        assert [(i.key, i.score) for i in r0.items] == \
            [(i.key, i.score) for i in r1.items]
    eng.close()


# -- dispatch ledger + compiled-program gates --------------------------------


@pytest.fixture(scope="module")
def mesh_engine():
    return _build("IVFPQ", MESH_PARAMS, warmup=[8])


def test_mesh_paths_launch_documented_dispatches(mesh_engine):
    eng, vecs = mesh_engine
    doc = perf_model.DOCUMENTED_DISPATCHES
    # no exact rerank wanted (SCANN reordering=false): scan and merge
    scan_eng, _ = _build("SCANN", {**MESH_PARAMS, "reordering": False},
                         n=1000)
    cases = {
        "ivfpq_mesh_fused": (eng, {"scan_mode": "full"}),
        "ivfpq_mesh_scan": (scan_eng, {"scan_mode": "full"}),
    }
    for path, (engine, params) in cases.items():
        ledger = _search(engine, vecs, index_params=params)
        assert ledger.tags == doc[path], (
            f"{path}: launched {ledger.tags}, documented {doc[path]}"
        )


def test_mesh_probe_regime_documented_dispatch(mesh_engine):
    """scan_mode=probe on a mesh partition keeps the row-sharded layout:
    one fused program gated to the probed cells, its own dispatch tag —
    it must NOT fall back to the single-device bucket scan."""
    eng, vecs = mesh_engine
    ledger = _search(eng, vecs,
                     index_params={"scan_mode": "probe", "nprobe": 8})
    assert ledger.tags == \
        perf_model.DOCUMENTED_DISPATCHES["ivfpq_mesh_probe"], ledger.tags


def test_mesh_three_stage_documented_dispatch_and_parity(rng):
    """IVFRABITQ under a mesh: bit planes, int8 mirror and raw base
    row-sharded in lockstep, the whole binary -> int8 -> exact chain is
    ONE shard_map program with its own documented tag. Results are not
    bit-identical to the single-device chain by design — each shard
    rescores its local top-min(r0, local_n) rather than the global
    top-r0's local slice — so the gate is ground-truth recall parity
    within a tight band, not bit equality."""
    from vearch_tpu.index.binary import IVFRaBitQIndex

    data = rng.standard_normal((N, D)).astype(np.float32)

    def build(ms):
        params = IndexParams("IVFRABITQ", MetricType.L2, {
            "ncentroids": 16, "train_iters": 4, "mesh_serving": ms,
        })
        store = RawVectorStore(D)
        store.add(data)
        idx = IVFRaBitQIndex(params, store)
        idx.train(data[:2000])
        idx.absorb(N)
        return idx

    solo, mesh = build("off"), build("on")
    q = data[:8] + 0.01 * rng.standard_normal((8, D)).astype(np.float32)
    ledger = perf_model.PerfLedger()
    ivf_ops.set_dispatch_ledger(ledger)
    try:
        ms, mi = mesh.search(q, 10, None, None)
    finally:
        ivf_ops.set_dispatch_ledger(None)
    assert ledger.tags == \
        perf_model.DOCUMENTED_DISPATCHES["ivfrabitq_mesh_three_stage"], \
        ledger.tags
    ss, si = solo.search(q, 10, None, None)
    # near-duplicate queries: both chains pin the true row at rank 1
    assert (mi[:, 0] == np.arange(8)).all(), mi[:, 0]
    assert (si[:, 0] == np.arange(8)).all(), si[:, 0]
    d2 = ((q[:, None, :].astype(np.float64)
           - data[None].astype(np.float64)) ** 2).sum(-1)
    gt = np.argsort(d2, axis=1)[:, :10]
    rec = lambda ids: np.mean([  # noqa: E731
        len(set(ids[j].tolist()) & set(gt[j].tolist())) / 10
        for j in range(8)])
    assert rec(mi) >= rec(si) - 0.05, (rec(mi), rec(si))
    assert rec(mi) >= 0.85 and rec(si) >= 0.85, (rec(mi), rec(si))


def test_mesh_probe_regime_recall(rng):
    """The probe regime under the mesh prunes to nprobe cells — recall
    against the ungated mesh scan stays high at moderate nprobe, and
    query-axis sharding doesn't change what the gate admits."""
    _, mesh, _ = _ivfpq_pair(rng)
    q = rng.standard_normal((8, D)).astype(np.float32)
    _, full_i = mesh.search(q, 10, None, {"scan_mode": "full"})
    _, probe_i = mesh.search(
        q, 10, None, {"scan_mode": "probe", "nprobe": 8})
    overlap = np.mean([
        len(set(full_i[r]) & set(probe_i[r])) / 10
        for r in range(q.shape[0])
    ])
    assert overlap >= 0.7, overlap
    _, probe_qa = mesh.search(
        q, 10, None,
        {"scan_mode": "probe", "nprobe": 8, "mesh_shape": "4x2"})
    assert np.array_equal(probe_i, probe_qa)


def test_mesh_full_scan_cliff_scales_with_data_axis(rng):
    """The auto full->probe cliff is a per-chip row budget: it scales by
    the DATA axis of the serving mesh, not the device count. Same index,
    same 8 devices — a 2x4 mesh holds 4x the rows per chip of an 8x1
    mesh, so its cliff sits at a quarter the total row count."""
    data = rng.standard_normal((N, D)).astype(np.float32)
    store = RawVectorStore(D)
    store.add(data)
    idx = IVFPQIndex(IndexParams("IVFPQ", MetricType.L2, {
        "ncentroids": 16, "nsubvector": 8, "train_iters": 4,
        "mesh_serving": "on", "full_scan_limit": 500,
    }), store)
    idx.train(data[:2000])
    idx.absorb(N)
    q = rng.standard_normal((4, D)).astype(np.float32)

    def route(params):
        ledger: list = []
        ivf_ops.set_dispatch_ledger(ledger)
        try:
            idx.search(q, 10, None, params)
        finally:
            ivf_ops.set_dispatch_ledger(None)
        return ledger

    # 8x1: budget 500*8 = 4000 >= 3000 rows -> stays in the full scan
    assert route({}) == \
        perf_model.DOCUMENTED_DISPATCHES["ivfpq_mesh_fused"]
    # 2x4: still 8 devices, but budget 500*2 = 1000 < 3000 -> probe
    # regime (counting all devices would wrongly keep this on full)
    assert route({"mesh_shape": "2x4"}) == \
        perf_model.DOCUMENTED_DISPATCHES["ivfpq_mesh_probe"]


def test_mesh_scan_only_path_scann_reordering_off(rng):
    """reordering=false (ScaNN semantics: pure quantized scores, no
    exact pass) on a mesh index launches the one-dispatch scan."""
    from vearch_tpu.index.scann import ScannIndex

    data = rng.standard_normal((1500, D)).astype(np.float32)
    store = RawVectorStore(D)
    store.add(data)
    idx = ScannIndex(IndexParams("SCANN", MetricType.INNER_PRODUCT, {
        "ncentroids": 16, "nsubvector": 8, "train_iters": 4,
        "reordering": False, "mesh_serving": "on",
    }), store)
    idx.train(data)
    idx.absorb(1500)
    ledger: list = []
    ivf_ops.set_dispatch_ledger(ledger)
    try:
        _, ids = idx.search(
            rng.standard_normal((4, D)).astype(np.float32), 10, None,
            {"scan_mode": "full"})
    finally:
        ivf_ops.set_dispatch_ledger(None)
    assert ledger == perf_model.DOCUMENTED_DISPATCHES["ivfpq_mesh_scan"]
    assert ids.shape == (4, 10) and np.all(ids >= 0)


def test_warmed_mesh_search_compiles_zero_new_programs(mesh_engine):
    eng, vecs = mesh_engine
    req = {"scan_mode": "full"}
    _search(eng, vecs, index_params=req)  # settle the exact shape
    before = perf_model.total_compiled_programs()
    for _ in range(3):
        ledger = _search(eng, vecs, index_params=req)
        assert ledger.tags == \
            perf_model.DOCUMENTED_DISPATCHES["ivfpq_mesh_fused"]
    assert perf_model.total_compiled_programs() == before, (
        "warmed same-shape mesh search retraced — the mesh program "
        "builders must cache per (mesh, statics)"
    )


def test_mesh_trace_reports_phases_and_placement(mesh_engine):
    eng, vecs = mesh_engine
    trace: dict = {}
    eng.search(SearchRequest(
        vectors={"emb": vecs[:8]}, k=10, include_fields=[],
        index_params={"scan_mode": "full"}, trace=trace))
    assert trace["perf_path"] == "ivfpq_mesh_fused"
    span_names = [s[0] for s in trace["_phase_spans"]]
    assert "mesh.place" in span_names
    assert trace["mesh"]["devices"] == 8
    emb = trace["mesh"]["fields"]["emb"]
    assert emb["data_shards"] == 8
    assert emb["per_device_bytes"] > 0
    # the place phase says what went up meanwhile (here: the query
    # batch), the dispatch when its jitted call returned
    place = next(s for s in trace["_phase_spans"] if s[0] == "mesh.place")
    assert place[3]["bytes"] >= 8 * D * 4
    kernel = next(s for s in trace["_phase_spans"]
                  if s[0] == "kernel.sharded_fused_scan_rerank")
    assert kernel[3]["rows"] == kernel[3]["bucket_rows"] == 8
    assert 0 < kernel[3]["launch_us"] <= kernel[2]


@pytest.mark.parametrize("index,params,tag,label,module", [
    ("IVFPQ", {"scan_mode": "full"}, "sharded_fused_scan_rerank",
     ("sharded.ivf_fused[", ",p0]"), "jit_sharded_fused_scan_rerank"),
    ("IVFPQ", {"scan_mode": "probe", "nprobe": 8},
     "sharded_probe_scan_rerank",
     ("sharded.ivf_fused[", ",p8]"), "jit_sharded_probe_scan_rerank"),
    ("SCANN", {"scan_mode": "full"}, "sharded_scan",
     ("sharded.int8[", "]"), "jit_run"),
])
def test_mesh_serving_program_is_named_on_the_device_trace(
        mesh_engine, index, params, tag, label, module):
    """XLA names a module after the jitted function, and the benchmark
    finds a dispatch on the device trace by that name
    (benchmark/kernels/sharded_fused_scan_rerank.py): the fused mesh
    program carries its dispatch tag, with its stages as named scopes;
    every other shard_map program of parallel/sharded.py is `jit_run`.
    Every mesh dispatch stamps `launch_us`. SCANN with
    `reordering: false` wants no rerank: scan and merge alone."""
    eng, vecs = mesh_engine if index == "IVFPQ" else _build(
        "SCANN", {**MESH_PARAMS, "reordering": False}, n=1000)
    trace: dict = {}
    eng.search(SearchRequest(vectors={"emb": vecs[:8]}, k=10,
                             include_fields=[], index_params=params,
                             trace=trace))
    kernels = [s for s in trace["_phase_spans"]
               if s[0].startswith("kernel.")]
    assert kernels[0][0] == f"kernel.{tag}"
    assert all("launch_us" in s[3] for s in kernels), kernels
    live = {f"jit_{fn.__name__}"
            for name, fn in perf_model._JIT_REGISTRY.items()
            if name.startswith(label[0]) and name.endswith(label[1])
            and fn._cache_size()}
    assert live == {module}, live


# -- incremental placement (tail-append, never full re-place) ----------------


def test_absorb_tail_appends_per_shard(rng):
    """Within cached capacity, absorb H2Ds exactly the align-rounded
    window of new rows — asserted against the bytes model, and the
    rebuild counter must not move."""
    _, mesh, _ = _ivfpq_pair(rng)
    q = rng.standard_normal((2, D)).astype(np.float32)
    mesh.search(q, 10, None)  # place
    mstats = mesh._mirror._sh_cache.stats
    # mirror capacity is 4096 (unit 512*8) at n=3000: +500 rows stays
    # within capacity → must append, not rebuild
    rebuilds = mstats["rebuilds"]
    bytes0 = mstats["h2d_bytes"]
    rows0 = mesh.indexed_count
    more = rng.standard_normal((500, D)).astype(np.float32)
    mesh.store.add(more)
    mesh.absorb(rows0 + 500)
    mesh.search(q, 10, None)
    assert mstats["rebuilds"] == rebuilds, "absorb re-placed the mirror"
    assert mstats["appends"] >= 1
    # bytes model: window [floor(rows0/512)*512, ceil(n/512)*512) of
    # (d int8 codes + scale f32 + vsq f32) per row
    lo = (rows0 // 512) * 512
    hi = -(-(rows0 + 500) // 512) * 512
    expect = (hi - lo) * (D + 8)
    assert mstats["h2d_bytes"] - bytes0 == expect, (
        f"mirror append moved {mstats['h2d_bytes'] - bytes0}b, "
        f"window model says {expect}b"
    )


def test_flat_sharded_absorb_appends(rng):
    data = rng.standard_normal((2000, D)).astype(np.float32)
    store = RawVectorStore(D)
    store.add(data)
    idx = ShardedFlatIndex(IndexParams("FLAT_SHARDED", MetricType.L2, {}),
                           store)
    idx.absorb(2000)
    q = rng.standard_normal((2, D)).astype(np.float32)
    idx.search(q, 5, None)
    # grow past capacity once (the rebuild establishes geometric
    # headroom), then further absorbs must land as appends
    store.add(rng.standard_normal((200, D)).astype(np.float32))
    idx.absorb(2200)
    idx.search(q, 5, None)
    rebuilds = idx.placement_stats()["rebuilds"]
    bytes0 = idx.placement_stats()["h2d_bytes"]
    store.add(rng.standard_normal((200, D)).astype(np.float32))
    idx.absorb(2400)
    idx.search(q, 5, None)
    stats = idx.placement_stats()
    assert stats["rebuilds"] == rebuilds, "absorb re-placed the buffer"
    assert stats["appends"] >= 1
    lo = (2200 // 128) * 128
    hi = -(-2400 // 128) * 128
    expect = (hi - lo) * (D * 4 + 4)  # f32 rows + derived sqnorm column
    assert stats["h2d_bytes"] - bytes0 == expect


# -- the raw shard placed as super-rows (ISSUE 30) ---------------------------

# d -> rows a device row of the sharded raw store (mesh.row_pack): whole
# 128-lane groups for a multiple of 16; 128 is row-major as it is; 100
# would need 32 and keeps [cap, d]
ROW_PACK = {96: 4, 64: 2, 128: 1, 100: 1}


@pytest.mark.parametrize("d", sorted(ROW_PACK))
def test_gather_from_the_packed_view_moves_bits(rng, d):
    """The shared tail's gather on the raw slab as placed returns
    exactly `base[ids]`: first and last rows, every sub-row."""
    import jax.numpy as jnp

    from vearch_tpu.parallel.sharded import _gather_rows

    pack = mesh_lib.row_pack(d)
    assert pack == ROW_PACK[d]
    n = 64 * pack
    base = rng.standard_normal((n, d)).astype(np.float32)
    ids = rng.integers(0, n, size=(4, 40)).astype(np.int32)
    ids[0, :8] = [0, 1, 2, 3, n - 4, n - 3, n - 2, n - 1]
    got = _gather_rows(jnp.asarray(base.reshape(n // pack, pack * d)),
                       jnp.asarray(ids), d)
    assert got.shape == (4, 40, d)
    assert np.array_equal(np.asarray(got), base[ids])


@pytest.mark.parametrize("d", sorted(ROW_PACK))
def test_mesh_rerank_on_the_packed_raw_shard(rng, d):
    """A mesh IVFPQ search reranks against the raw shard as placed
    (`[cap / pack, pack * d]`) and agrees bit for bit with the
    single-device fused search, through every way rows reach the
    shard: first placement, growth past capacity, a tail append across
    a shard boundary, rows overwritten below the high-water mark."""
    from vearch_tpu.ops.distance import host_sqnorms

    pack = ROW_PACK[d]
    n0 = 1001  # not a multiple of any pack
    single, mesh, _ = _ivfpq_pair(rng, n=n0, d=d, train_rows=n0,
                                  nsubvector=4)
    q = rng.standard_normal((4, d)).astype(np.float32)

    def agree(mask=None):
        ss, si = single.search(q, 10, mask)
        ms, mi = mesh.search(q, 10, mask)
        assert np.array_equal(si, mi)
        assert np.array_equal(ss, ms)
        # the placed shard is the host rows, the column their sqnorms
        cache = mesh.store._sh_cache
        (base,) = cache.arrays
        cap = cache.sqnorm.shape[0]
        assert base.shape == (cap // pack, pack * d)
        host = np.zeros((cap, d), np.float32)
        host[:mesh.store.count] = mesh.store.host_view()
        assert np.array_equal(np.asarray(base).reshape(cap, d), host)
        assert np.array_equal(np.asarray(cache.sqnorm), host_sqnorms(host))
        return mesh.mesh_info()["raw_placement"]

    def grow(rows):
        more = rng.standard_normal((rows, d)).astype(np.float32)
        for idx in (single, mesh):
            idx.store.add(more)
            idx.absorb(idx.store.count)

    # (i) first placement: 8 shards x 128 rows hold 1001
    placed = agree()
    assert placed["row_pack"] == pack
    assert (placed["rebuilds"], placed["appends"]) == (1, 0)
    # (iii) growth past capacity re-places at twice the capacity
    grow(100)
    grown = agree()
    assert (grown["rebuilds"], grown["appends"]) == (2, 0)
    assert mesh.store._sh_cache.sqnorm.shape[0] == 2048
    # (ii) a tail append inside capacity, over the boundary between
    # shards 4 and 5 (row 1280): the window's rows and nothing else
    grow(300)
    appended = agree()
    assert (appended["rebuilds"], appended["appends"]) == (2, 1)
    assert appended["h2d_bytes"] - grown["h2d_bytes"] == \
        (1408 - 1024) * (4 * d + 4)
    # (iv) rows rewritten below the high-water mark and re-absorbed,
    # then the best hits deleted: masked on every shard
    lo, n = 130, mesh.store.count
    fresh = rng.standard_normal((3, d)).astype(np.float32)
    for idx in (single, mesh):
        idx.store._host[lo:lo + 3] = fresh
        idx.indexed_count = lo
        idx.absorb(n)
    single.store._device_rows = lo
    mesh.store._sh_cache.lower_rows(lo)
    redone = agree()
    assert (redone["rebuilds"], redone["appends"]) == (2, 2)
    _, top = single.search(q, 10, None)
    mask = np.ones(n, dtype=bool)
    mask[np.unique(top[:, :4])] = False
    agree(mask)
    assert not set(np.unique(top[:, :4])) & set(
        mesh.search(q, 10, mask)[1].ravel().tolist())


def test_mesh_three_stage_reranks_on_the_packed_raw_shard(rng):
    """`index/binary.py`'s mesh chain ends in the same tail: at 96
    dimensions it reranks against four rows a device row, and the exact
    scores it returns are the rows' own."""
    from vearch_tpu.index.binary import IVFRaBitQIndex

    d, n = 96, 2001
    data = rng.standard_normal((n, d)).astype(np.float32)
    store = RawVectorStore(d)
    store.add(data)
    idx = IVFRaBitQIndex(IndexParams("IVFRABITQ", MetricType.L2, {
        "ncentroids": 16, "train_iters": 4, "mesh_serving": "on"}), store)
    idx.train(data)
    idx.absorb(n)
    q = data[:8] + 0.01 * rng.standard_normal((8, d)).astype(np.float32)
    scores, ids = idx.search(q, 10, None, None)
    assert idx.mesh_info()["raw_placement"]["row_pack"] == 4
    assert store._sh_cache.arrays[0].shape[1] == 4 * d
    assert (ids[:, 0] == np.arange(8)).all(), ids[:, 0]
    exact = -((q[:, None, :] - data[ids]) ** 2).sum(-1)
    assert np.allclose(scores, exact, rtol=1e-4, atol=1e-4)


def test_mesh_construction_cached_per_device_count():
    """Repeated publishes must reuse the same Mesh object — the program
    builders key on mesh identity, so a fresh Mesh would retrace."""
    assert mesh_lib.make_mesh(4) is mesh_lib.make_mesh(4)
    assert mesh_lib.make_mesh(8) is mesh_lib.default_mesh()
    assert mesh_lib.make_mesh(8, query_axis=2) is \
        mesh_lib.make_mesh(8, query_axis=2)
    assert mesh_lib.make_mesh(4) is not mesh_lib.make_mesh(8)


# -- per-device HBM footprint model ------------------------------------------


def test_per_device_footprint_divides_sharded_state(rng):
    single, mesh, _ = _ivfpq_pair(rng)
    q = rng.standard_normal((2, D)).astype(np.float32)
    mesh.search(q, 10, None)
    per_dev = mesh.device_footprint_per_device_bytes()
    total = mesh.device_footprint_bytes()
    assert 0 < per_dev < total
    # model identity: replicated + ceil(sharded / n_shards)
    assert perf_model.per_device_bytes(800, 100, 8) == 200
    assert perf_model.per_device_bytes(801, 0, 8) == 101
    assert perf_model.per_device_bytes(800, 100, 1) == 900
    # single-device index reports the whole footprint per device
    assert single.device_footprint_per_device_bytes() == \
        single.device_footprint_bytes()


def test_mesh_serving_config_validation():
    store = RawVectorStore(D)
    with pytest.raises(ValueError):
        IVFPQIndex(IndexParams("IVFPQ", MetricType.L2, {
            "ncentroids": 4, "nsubvector": 8, "mesh_serving": "sideways",
        }), store)
    for given, read in ((True, "on"), (False, "off"), ("AUTO", "auto")):
        idx = IVFPQIndex(IndexParams("IVFPQ", MetricType.L2, {
            "ncentroids": 4, "nsubvector": 8, "mesh_serving": given,
        }), store)
        assert idx.mesh_serving == read


def test_apply_config_toggles_mesh_serving():
    eng, vecs = _build("IVFPQ", dict(MESH_PARAMS, mesh_serving="off"),
                       n=1000)
    ledger = _search(eng, vecs, index_params={"scan_mode": "full"})
    assert ledger.tags == \
        perf_model.DOCUMENTED_DISPATCHES["ivfpq_full_fused"]
    eng.apply_config({"mesh_serving": "on"})
    ledger = _search(eng, vecs, index_params={"scan_mode": "full"})
    assert ledger.tags == \
        perf_model.DOCUMENTED_DISPATCHES["ivfpq_mesh_fused"]
    eng.close()


# -- concurrency -------------------------------------------------------------


def test_mesh_concurrent_search_absorb(rng):
    """Concurrent searches and absorbs on a mesh-serving engine: the
    lock-free reference-swap publication of sharded buffers must never
    produce an error or an inconsistent result."""
    schema = TableSchema("t", fields=[
        FieldSchema("emb", DataType.VECTOR, dimension=D,
                    index=IndexParams("IVFPQ", MetricType.L2,
                                      dict(MESH_PARAMS))),
    ], refresh_interval_ms=20)
    eng = Engine(schema)
    eng.start_refresh_loop()
    vecs = rng.standard_normal((4000, D)).astype(np.float32)
    eng.upsert([{"_id": f"s{i}", "emb": vecs[i]} for i in range(1500)])
    eng.wait_for_index(timeout=300)

    errors: list[Exception] = []
    stop = threading.Event()

    def writer():
        try:
            for b in range(10):
                base = 1500 + b * 200
                eng.upsert([
                    {"_id": f"w{base + i}", "emb": vecs[base + i]}
                    for i in range(200)
                ])
        except Exception as e:
            errors.append(e)

    def searcher():
        try:
            while not stop.is_set():
                res = eng.search(SearchRequest(
                    vectors={"emb": vecs[:4]}, k=5,
                    index_params={"scan_mode": "full"}))
                assert len(res) == 4
                assert len(res[0].items) == 5
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=writer, daemon=True)]
    threads += [threading.Thread(target=searcher, daemon=True)
                for _ in range(2)]
    for t in threads:
        t.start()
    threads[0].join(timeout=300)
    stop.set()
    for t in threads[1:]:
        t.join(timeout=120)
    assert not errors, errors
    # the stress really exercised the sharded placement
    stats = eng.indexes["emb"]._mirror._sh_cache.stats
    assert stats["rebuilds"] + stats["appends"] >= 1
    eng.close()


def test_mesh_cluster_stress_under_lockcheck(tmp_path, rng):
    """VEARCH_LOCKCHECK=1 stress against the cluster layer with a
    mesh-serving space: every ps/raft/wal/querycache lock becomes a
    named DebugLock, and concurrent writes (→ absorb tail-appends on
    the mesh placement) racing cache-bypassing full-scan searches must
    leave the recorder with zero violations."""
    from vearch_tpu.cluster.master import MasterServer
    from vearch_tpu.cluster.ps import PSServer
    from vearch_tpu.cluster.router import RouterServer
    from vearch_tpu.sdk.client import VearchClient
    from vearch_tpu.tools import lockcheck

    lockcheck.reset()
    lockcheck.enable()  # BEFORE construction: locks are minted at init
    master = ps = router = None
    try:
        master = MasterServer(heartbeat_ttl=3600.0)
        master.start()
        ps = PSServer(data_dir=str(tmp_path / "ps0"),
                      master_addr=master.addr,
                      heartbeat_interval=0.3,
                      flush_interval=3600.0, raft_tick=0.3)
        ps.start()
        router = RouterServer(master_addr=master.addr)
        router.start()

        cl = VearchClient(router.addr)
        cl.create_database("db")
        cl.create_space("db", {
            "name": "s", "partition_num": 1, "replica_num": 1,
            "fields": [{"name": "emb", "data_type": "vector",
                        "dimension": D,
                        "index": {"index_type": "IVFPQ",
                                  "metric_type": "L2",
                                  "params": dict(MESH_PARAMS)}}],
        })
        vecs = rng.standard_normal((1200, D)).astype(np.float32)
        cl.upsert("db", "s", [{"_id": f"seed{i}", "emb": vecs[i].tolist()}
                              for i in range(400)])
        for eng in ps.engines.values():
            eng.wait_for_index(timeout=300)

        errors: list[Exception] = []
        stop = threading.Event()

        def writer(tid: int):
            try:
                for b in range(4):
                    base = 400 + tid * 400 + b * 100
                    cl.upsert("db", "s", [
                        {"_id": f"w{tid}_{base + i}",
                         "emb": vecs[base + i].tolist()}
                        for i in range(100)
                    ])
            except Exception as e:
                errors.append(e)

        def searcher(sid: int):
            try:
                i = 0
                while not stop.is_set():
                    out = cl.search(
                        "db", "s",
                        [{"field": "emb",
                          "feature": vecs[(sid * 7 + i) % 400]}],
                        limit=3,
                        index_params={"scan_mode": "full"},
                        cache=False)  # hammer the engine, not the cache
                    assert len(out) == 1 and len(out[0]) == 3
                    i += 1
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=writer, args=(t,),
                                    daemon=True, name=f"mesh-w{t}")
                   for t in range(2)]
        threads += [threading.Thread(target=searcher, args=(i,),
                                     daemon=True, name=f"mesh-s{i}")
                    for i in range(2)]
        for t in threads:
            t.start()
        for t in threads[:2]:
            t.join(timeout=300)
        stop.set()
        for t in threads[2:]:
            t.join(timeout=120)

        assert not errors, errors
        # the mesh data plane really served: placement happened
        eng = next(iter(ps.engines.values()))
        info = eng.mesh_info()
        assert info is not None and info["devices"] == 8
        edges = lockcheck.acquisition_edges()
        assert edges, "no DebugLock edges recorded — lockcheck inert?"
        lockcheck.check()  # zero inversions / unguarded writes / misuse
    finally:
        if router is not None:
            router.stop()
        if ps is not None:
            try:
                ps.stop(flush=False)
            except Exception:
                pass
        if master is not None:
            master.stop()
        lockcheck.reset()


# -- router -> PS end-to-end -------------------------------------------------


def test_mesh_space_end_to_end(tmp_path):
    """A space with mesh serving on serves search/upsert/delete through
    router -> PS with results identical to a mesh-off space holding the
    same rows, and /ps/stats + /metrics expose the mesh data plane."""
    from vearch_tpu.cluster.standalone import StandaloneCluster
    from vearch_tpu.sdk.client import VearchClient

    c = StandaloneCluster(data_dir=str(tmp_path / "cluster"), n_ps=1)
    c.start()
    try:
        cl = VearchClient(c.router_addr)
        cl.create_database("db")
        rng = np.random.default_rng(7)
        vecs = rng.standard_normal((600, D)).astype(np.float32)

        def mk_space(name, mesh_serving):
            cl.create_space("db", {
                "name": name, "partition_num": 1, "replica_num": 1,
                "fields": [
                    {"name": "emb", "data_type": "vector", "dimension": D,
                     "index": {"index_type": "IVFPQ", "metric_type": "L2",
                               "params": dict(MESH_PARAMS,
                                              mesh_serving=mesh_serving)}},
                ],
            })
            cl.upsert("db", name, [
                {"_id": f"d{i}", "emb": vecs[i].tolist()}
                for i in range(600)
            ])

        mk_space("mesh_on", "on")
        mk_space("mesh_off", "off")
        ps = c.ps_nodes[0]
        for eng in ps.engines.values():
            eng.wait_for_index(timeout=300)

        def hits(space, q, limit=10):
            out = cl.search("db", space,
                            [{"field": "emb", "feature": q}], limit=limit,
                            index_params={"scan_mode": "full"},
                            cache=False)
            return [(h["_id"], round(h["_score"], 4)) for h in out[0]]

        q = vecs[13]
        on, off = hits("mesh_on", q), hits("mesh_off", q)
        assert on == off
        assert on[0][0] == "d13"

        # delete reflects across shards
        cl.delete("db", "mesh_on", ["d13"])
        cl.delete("db", "mesh_off", ["d13"])
        on, off = hits("mesh_on", q), hits("mesh_off", q)
        assert on == off
        assert all(h[0] != "d13" for h in on)

        # upsert lands through the tail-append path
        newv = rng.standard_normal(D).astype(np.float32)
        for space in ("mesh_on", "mesh_off"):
            cl.upsert("db", space, [{"_id": "fresh", "emb": newv.tolist()}])
        on, off = hits("mesh_on", newv), hits("mesh_off", newv)
        assert on == off
        assert on[0][0] == "fresh"

        # observability surfaces: /ps/stats mesh block + devices gauge
        stats = ps._h_stats(None, None)
        mesh_blocks = [
            p["mesh"] for p in stats["partitions"].values()
            if p["mesh"] is not None
        ]
        assert mesh_blocks and mesh_blocks[0]["devices"] == 8
        metrics_text = ps.server.metrics.render()
        assert "vearch_engine_mesh_devices" in metrics_text
    finally:
        c.stop()
