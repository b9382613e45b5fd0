"""Compile the main-path programs for the chip, without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described and not attached (v5e:2x2). Each case lowers one jitted entry
point of the served IVFPQ path at the widths `chip_smoke.py` serves
(d=128, the mirror/store capacities the code picks for 1,000,000 rows,
nlist=2048, m=32, rerank 256 and 128, k=10 at its fetch-k tier) and compiles it:
what the chip's compiler refuses — a block shape Mosaic cannot tile, a
program that does not fit 16 GB — fails HERE and costs no chip time.
A compile that passes is not a chip run and says nothing about speed.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under xdist
every worker imports this file but only one runs it. Keep every such
test in THIS file (on-chip-measurement guide, section 2).
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from vearch_tpu.engine.raw_vector import RawVectorStore
from vearch_tpu.engine.types import IndexParams, MetricType
from vearch_tpu.index.int8_mirror import Int8Mirror
from vearch_tpu.index import ivf as ivf_index
from vearch_tpu.index.ivf import IVFPQIndex
from vearch_tpu.ops import binary_scan as binary_ops
from vearch_tpu.ops import ivf as ivf_ops
from vearch_tpu.ops import kmeans as km
from vearch_tpu.ops import pallas_kernels, perf_model
from vearch_tpu.ops import pq as pq_ops
from vearch_tpu.ops.distance import brute_force_search
from vearch_tpu.parallel import sharded
from vearch_tpu.parallel.mesh import ShardedRowCache, row_pack

ROWS, D, K = 1_000_000, 128, 10
RERANK, RERANK_SHALLOW = 256, 128  # chip_smoke.py's two depths
HBM_BYTES = 16e9  # one v5e chip
L2 = MetricType.L2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def widths():
    """What the code itself picks for the smoke's deployment: the index
    defaults, the int8 mirror's and the raw store's capacity after
    1,000,000 rows arrive in the smoke's 5,000-row batches (1-wide
    stand-ins: capacity growth does not depend on the dimension)."""
    index = IVFPQIndex(
        IndexParams("IVFPQ", L2, {"ncentroids": 2048, "nsubvector": 32}),
        RawVectorStore(D))
    mirror = Int8Mirror(1)
    mirror.append_quantized(np.zeros((ROWS, 1), np.int8),
                            np.zeros(ROWS, np.float32),
                            np.zeros(ROWS, np.float32))
    store = RawVectorStore(1)
    for _ in range(ROWS // 5000):
        store.add(np.zeros((5000, 1), np.float32))
    return {
        "n_mirror": mirror._h8.shape[0],   # 512-aligned
        "n_store": store.capacity,         # doubling
        "nlist": index.nlist, "m": index.m, "ksub": index.ksub,
        "nprobe": index.default_nprobe, "sample": index.train_sample,
        "iters": index.train_iters,
        "fetch_k": perf_model.bucket_fetch_k(K),
    }


def _fused_args(S, b, n_mirror, n_store):
    return (S((b, D), jnp.float32), S((n_mirror, D), jnp.int8),
            S((n_mirror,), jnp.float32), S((n_mirror,), jnp.float32),
            S((n_mirror,), jnp.bool_), S((n_store, D), jnp.float32),
            S((n_store,), jnp.float32))


def _shapes(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


def _report(name, compiled):
    m = compiled.memory_analysis()
    args, temp = m.argument_size_in_bytes, m.temp_size_in_bytes
    print(f"{name}: arguments {args / 1e9:.3f} GB, temp {temp / 1e9:.3f} GB")
    assert args + temp < HBM_BYTES, (name, args, temp)
    return args, temp


def test_widths_are_the_ones_the_code_picks(widths):
    assert widths["n_mirror"] == 1_000_448 and widths["n_store"] == 1 << 20
    assert (widths["nlist"], widths["m"], widths["ksub"]) == (2048, 32, 256)
    assert widths["fetch_k"] == 16 and widths["sample"] == 262_144


def _entry_instructions(compiled):
    """(name, result shape, opcode, rest of the line) of every
    instruction of the compiled program's entry computation: a fusion
    counts once, as the chip runs it."""
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    return re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$",
        entry, re.M)


def _score_sized(compiled, b, n, dtype=r"\w+"):
    """Names of the instructions whose result holds b*n elements (of
    `dtype`, by default any) or more: what writes, copies or relays a
    [B, N] score matrix. A bitcast moves nothing."""
    return [
        name for name, shape, op, _ in _entry_instructions(compiled)
        if op not in ("bitcast", "parameter", "get-tuple-element", "tuple")
        and any(np.prod([int(x) for x in dims.split(",")]) >= b * n
                for dims in re.findall(rf"\b{dtype}\[([\d,]+)\]", shape))]


def _widest_sort_input(compiled, b):
    """Columns of the widest [b, columns] array a `sort` or a `TopK`
    custom call of the program takes."""
    instructions = _entry_instructions(compiled)
    shape_of = {name: shape for name, shape, _, _ in instructions}
    widest = 0
    for _, _, op, rest in instructions:
        if op == "sort" or (op == "custom-call" and '"TopK"' in rest):
            for name in re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0]):
                for cols in re.findall(rf"\[{b},(\d+)\]",
                                       shape_of.get(name, "")):
                    widest = max(widest, int(cols))
    return widest


@pytest.mark.parametrize("b,r", [(8, RERANK), (64, RERANK), (256, RERANK),
                                 (1024, RERANK), (64, RERANK_SHALLOW)])
def test_fused_scan_rerank_compiles(one_chip, widths, b, r):
    """The default hot path at every row bucket of the scheduler
    (`perf_model.ROW_BUCKETS`). The [B, N] f32 score matrix is written
    once and never copied: ONE instruction of the program produces a
    score-sized array (none at B=8, where the compiler fuses the matrix
    away), and temp holds one such buffer, not two. A blocked view of
    the matrix that is no bitcast of its tiles shows here as a second
    instruction (`reshape`, `copy`, `copy_bitcast_fusion`) before it
    costs a dispatch a third of its time on the chip."""
    assert {8, 64, 256, 1024} == set(perf_model.ROW_BUCKETS)
    n = widths["n_mirror"]
    compiled = ivf_ops.int8_scan_rerank.lower(
        *_fused_args(_shapes(one_chip), b, n, widths["n_store"]),
        r, widths["fetch_k"], scan_metric=L2, rerank_metric=L2,
        storage="int8").compile()
    _, temp = _report(f"int8_scan_rerank[B={b},r={r}]", compiled)
    matrix = perf_model.scan_peak_bytes(b, n)
    big = _score_sized(compiled, b, n)
    assert len(big) <= 1, big
    if b > 8:
        assert len(big) == 1 and matrix <= temp < 1.25 * matrix, (big, temp)
    # the widest sort or `TopK` is over the block maxima or the 16 r
    # group maxima of a query, never over a row or the r * BLOCK
    # gathered scores
    assert 0 < _widest_sort_input(compiled, b) \
        <= perf_model.select_width(r, n) < r * ivf_ops.BLOCK


def _probe_args(S, b, nlist, cap, n_valid):
    return (S((b, D), jnp.float32), S((nlist, D), jnp.float32),
            S((nlist, cap, D), jnp.int8), S((nlist,), jnp.float32),
            S((nlist, cap), jnp.float32), S((nlist, cap), jnp.int32),
            S((n_valid,), jnp.bool_))


# cap: the smallest 128-multiple holding 1M/2048 rows per list, and a
# skewed publish four times that (IVFPQIndex._bucket_shape)
@pytest.mark.parametrize("b,cap", [(8, 512), (64, 512), (64, 2048)])
def test_pallas_probe_kernel_compiles(one_chip, widths, monkeypatch, b, cap):
    """The TPU-default probe kernel, compiled by Mosaic (steered from
    here: jax.default_backend() is still the CPU in this process)."""
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    assert cap % 128 == 0 and cap >= ROWS / widths["nlist"]
    compiled = pallas_kernels.ivfpq_probe_search_pallas.lower(
        *_probe_args(_shapes(one_chip), b, widths["nlist"], cap,
                     widths["n_store"]),
        widths["nprobe"], RERANK, True).compile()
    _report(f"ivfpq_probe_search_pallas[B={b},cap={cap}]", compiled)
    assert "tpu_custom_call" in compiled.as_text()


def test_xla_probe_scan_and_rerank_compile(one_chip, widths):
    """`probe_kernel: "xla"` and the exact rerank both probe kernels
    hand their candidates to."""
    S = _shapes(one_chip)
    _report("ivfpq_candidates[B=64]", ivf_ops.ivfpq_candidates.lower(
        *_probe_args(S, 64, widths["nlist"], 512, widths["n_store"]),
        widths["nprobe"], RERANK, L2).compile())
    _report("exact_rerank[B=64]", ivf_ops.exact_rerank.lower(
        S((64, D), jnp.float32), S((64, RERANK), jnp.int32),
        S((widths["n_store"], D), jnp.float32),
        S((widths["n_store"],), jnp.float32),
        widths["fetch_k"], L2).compile())


def _flat_args(S, b, nlist, cap):
    return (S((b, D), jnp.float32), S((nlist, D), jnp.float32),
            S((nlist, cap, D), jnp.float32), S((nlist, cap), jnp.float32),
            S((nlist, cap), jnp.int32), S((nlist, cap), jnp.bool_))


# benchmark/configs/sift1m-ivfflat.json: nlist 1024, nprobe 32, r 256.
# cap: one tile (what ISSUE 32 expected of 1M rows), the 8192 the chip
# run published (longest list 7,824, seed 3200000011), and a longer
# list's 12288 at the mix's widest bucket
@pytest.mark.parametrize("b,cap", [(64, 2048), (64, 8192), (256, 12288)])
def test_ivfflat_probe_scan_compiles_in_tiles(one_chip, widths, b, cap):
    """The serving program of `sift1m-ivfflat`: XLA module
    `jit_ivfflat_candidates` (what the benchmark finds it by on the
    device trace), its stages under their scopes, and no instruction
    that writes more than ONE scan step's [B, tile, d] gather: no
    [B, nprobe, cap, d], and no slice of the [nlist, cap, d] table.

    Untiled (a step gathering [B, cap, d] whole) the chip's compiler
    keeps the gather in one piece up to 1 MiB a slice (cap 2048: temp
    0.001 GB) and past that cuts it into column slices of the WHOLE
    table, copied in every step: at cap 10368 six `mini-gather-slice`
    results of [1024, 1792, 128] f32 and 4.7 GB of temp beside a 5.4 GB
    table (PERF.md section 6, PR 32). `probe_tile` keeps every step's
    slice inside the 1 MiB, so temp stays under the step's own gather
    plus the running top list whatever the longest list.

    The validity mask arrives slot-major, `pred[nlist, cap]`, and a
    step gathers it by list row as it gathers the ids (PR 33): the
    module has no docid-indexed `pred[n_store]` operand, and no gather
    of single mask elements out of a 1-d operand (the 131,072 lookups
    a step that were 77 % of the program on the chip, PERF.md section
    6)."""
    nlist, nprobe = 1024, 32
    tile = ivf_ops.probe_tile(cap, D * 4)
    assert tile == min(cap, 2048) == ivf_ops.probe_tile_rows(D * 4)
    compiled = ivf_ops.ivfflat_candidates.lower(
        *_flat_args(_shapes(one_chip), b, nlist, cap),
        nprobe, RERANK, L2).compile()
    _, temp = _report(f"ivfflat_candidates[B={b},cap={cap}]", compiled)
    text = compiled.as_text()
    assert text.startswith("HloModule jit_ivfflat_candidates")
    for scope in ("coarse", "gather", "score", "fold"):
        assert f"/{scope}/" in text, scope
    entry = text.splitlines()[0]  # the module's own operands
    assert f"pred[{nlist},{cap}]" in entry
    assert not re.search(r"pred\[\d+\]", entry), "a 1-d mask operand"
    assert f"pred[{widths['n_store']}]" not in text
    mask_gathers = [
        (shape, operand) for shape, operand in re.findall(
            r"= (pred\[[\d,]+\])\S* gather\(%?([\w.\-]+)", text)]
    assert mask_gathers, "the mask is gathered somewhere"
    for shape, operand in mask_gathers:
        # by list row, out of the [nlist * tiles, tile] view
        assert shape == f"pred[{b},{tile}]", (shape, operand)
        (op_shape,) = set(re.findall(
            rf"%?{re.escape(operand)} = (pred\[[\d,]+\])", text))
        assert op_shape == f"pred[{nlist * cap // tile},{tile}]", op_shape
    step_gather = b * tile * D
    written = [
        (name, shape) for name, shape, op in re.findall(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(",
            text, re.M)
        if op not in ("bitcast", "parameter", "get-tuple-element", "tuple",
                      "while")
        and any(np.prod([int(x) for x in dims.split(",")]) > step_gather
                for dims in re.findall(r"\b\w+\[([\d,]+)\]", shape))]
    assert not written, written
    top_list = b * RERANK * (4 + 4)
    assert temp < 1.25 * (b * cap * D * 4 + top_list), temp
    # stronger, as compiled today: under two steps' own gathers
    assert temp < 2 * step_gather * 4 + (64 << 20), temp


# benchmark/configs/gist1m-960-ivfrabitq.json: 960-d, r0 512 (the
# product's default), r1 256, the fetch-k tier of k = 10; the published
# corpus (1,000,000 rows) and the cell's cut of it (500,000)
GIST_ROWS, GIST_CELL_ROWS, GIST_D, GIST_R0 = 1_000_000, 500_000, 960, 512


def _refine_args(S, b, rows, packed: bool):
    """`binary_refine_rerank`'s arguments at the capacities the code
    picks for the restored corpus (the mirrors 512-aligned, the store
    exactly its rows), the int8 rows and the raw store as placed for a
    gather (`packed`: `[n / 2, 1920]`, `row_pack(960)` 2) or as plain
    `[n, 960]`."""
    n = -(-rows // 512) * 512
    pack = row_pack(GIST_D) if packed else 1
    return n, (
        S((b, GIST_D), jnp.float32), S((n, GIST_D // 8), jnp.uint8),
        S((n,), jnp.float32), S((n,), jnp.float32),
        S((n // pack, pack * GIST_D), jnp.int8),
        S((n,), jnp.float32), S((n,), jnp.float32), S((n,), jnp.bool_),
        S((rows // pack, pack * GIST_D), jnp.float32),
        S((rows,), jnp.float32))


def _as_large_as_a_store(compiled, n, d):
    """Entry instructions whose result holds n * d elements or more, of
    any type: an unpacked +-1 operand, a copy of the int8 rows or of the
    raw store. Asynchronous prefetches (`copy-start` / `-done`) of a
    parameter move nothing the program would not read anyway."""
    return [name for name in _score_sized(compiled, n, d)
            if not name.startswith(("copy-start", "copy-done"))]


@pytest.mark.parametrize("rows,b", [
    (GIST_CELL_ROWS, 8), (GIST_CELL_ROWS, 64), (GIST_CELL_ROWS, 256),
    (GIST_ROWS, 64), (GIST_ROWS, 256)])
def test_three_stage_refinement_compiles_without_a_whole_store_copy(
        one_chip, widths, rows, b):
    """The serving program of `gist1m-960-ivfrabitq` at every row bucket
    its mix warms (64, 128 -> 256, 256) and the write check's 8, at the
    cell's rows and at the published corpus's: XLA
    module `jit_binary_refine_rerank` (what the benchmark finds it by on
    the device trace), its five stages under their scopes, and what was
    mended for the chip (PERF.md section 6, PR 34):

    - the bit planes' unpack is fused into stage 0's product: NO
      instruction writes an `[N, 960]` array, of bf16 or any other type
      (the unpacked operand would be 1.92 GB, 16x the planes);
    - ONE instruction writes the `[B, N]` f32 score matrix, and temp is
      that matrix (at B=8 not even that);
    - 960 is no multiple of 128, so the chip lays `[N, 960]` int8 rows
      and float32 rows out column-major: handed in like that, stage 1's
      and stage 2's row gathers each copy their whole operand row-major
      in every dispatch (0.96 + 3.84 GB: 16.4 of 24.8 ms on the chip).
      As placed for a gather, `[N / 2, 1920]` super-rows
      (`Int8Mirror.flush(packed=True)`, `RawVectorStore.device_buffer(
      packed=True)`), both parameters are row-major and no instruction
      is as large as either."""
    S = _shapes(one_chip)
    n, args = _refine_args(S, b, rows, packed=True)
    assert row_pack(GIST_D) == 2 and n in (500_224, 1_000_448)
    assert perf_model.refine_depths(widths["fetch_k"], rows) == (
        GIST_R0, 160)
    compiled = binary_ops.binary_refine_rerank.lower(
        *args, GIST_R0, RERANK, widths["fetch_k"], scan_metric=L2,
        rerank_metric=L2, storage="int8").compile()
    _, temp = _report(f"binary_refine_rerank[{rows} x 960, B={b}]",
                      compiled)
    text = compiled.as_text()
    assert text.startswith("HloModule jit_binary_refine_rerank")
    for scope in ("unpack", "stage0_score", "stage0_select",
                  "stage1_rescore", "rerank"):
        assert f"/{scope}/" in text, scope
    entry = text.splitlines()[0]  # the module's own operands, as placed
    assert f"s8[{n // 2},{2 * GIST_D}]{{1,0:" in entry
    assert f"f32[{rows // 2},{2 * GIST_D}]{{1,0:" in entry
    assert _as_large_as_a_store(compiled, rows, GIST_D) == []
    matrix = perf_model.scan_peak_bytes(b, n)
    assert temp < 1.25 * matrix, (temp, matrix)
    if b > 8:  # at 8 rows the matrix is smaller than a stage's gather
        written = _score_sized(compiled, b, n, "f32")
        assert len(written) == 1 and matrix <= temp, (written, temp)
    # stage 0 selects r0 = 512 through `_blocked_topk`: the widest sort
    # is over the 16 r0 group maxima of a query (8,192), never a row or
    # the r0 * BLOCK gathered scores (65,536)
    assert 0 < _widest_sort_input(compiled, b) \
        <= perf_model.select_width(GIST_R0, n) == 8_192


def test_three_stage_refinement_on_plain_layouts_copies_both_stores(
        one_chip, widths):
    """Why the two stores are placed as super-rows: the same program
    handed `[N, 960]` int8 rows and float32 rows (how the parent placed
    them) compiles to a row-major `copy` of each, whole, before its
    gather: two instructions as large as a store, and 4.1 GB of temp."""
    n, args = _refine_args(_shapes(one_chip), 64, GIST_ROWS, packed=False)
    compiled = binary_ops.binary_refine_rerank.lower(
        *args, GIST_R0, RERANK, widths["fetch_k"], scan_metric=L2,
        rerank_metric=L2, storage="int8").compile()
    _, temp = _report("binary_refine_rerank[B=64, plain layouts]", compiled)
    entry = compiled.as_text().splitlines()[0]
    assert f"s8[{n},{GIST_D}]{{0,1:" in entry  # column-major as placed
    assert f"f32[{GIST_ROWS},{GIST_D}]{{0,1:" in entry
    copies = _as_large_as_a_store(compiled, GIST_ROWS, GIST_D)
    assert len(copies) == 2 and all(c.startswith("copy") for c in copies)
    assert temp > GIST_ROWS * GIST_D * 4


@pytest.mark.parametrize("step", ["train_kmeans", "assign_sample",
                                  "assign_all_rows", "train_pq",
                                  "encode_pq"])
def test_build_steps_compile(one_chip, widths, step):
    """What a build dispatches, whatever its row count: coarse k-means
    over the training sample, assignment of the sample and of every row
    at absorb, PQ codebook training on the sample's residuals, PQ encode
    of all rows (the rows in pieces of the sample's size)."""
    assert ivf_index.BULK_ROWS == widths["sample"]
    S = _shapes(one_chip)
    sample, nlist, iters = widths["sample"], widths["nlist"], widths["iters"]
    cents = S((nlist, D), jnp.float32)
    books = S((widths["m"], widths["ksub"], D // widths["m"]), jnp.float32)
    lowered = {
        "train_kmeans": lambda: km.train_kmeans.lower(
            S((sample, D), jnp.float32), k=nlist, iters=iters),
        "assign_sample": lambda: km.assign_clusters.lower(
            S((sample, D), jnp.float32), cents),
        # absorb sends the rows up in pieces of BULK_ROWS, the sample's
        # shape: the build compiles no program of the partition's size
        "assign_all_rows": lambda: km.assign_clusters.lower(
            S((ivf_index.BULK_ROWS, D), jnp.float32), cents),
        "train_pq": lambda: jax.jit(functools.partial(
            pq_ops.train_pq, m=widths["m"], ksub=widths["ksub"],
            iters=iters)).lower(S((sample, D), jnp.float32)),
        "encode_pq": lambda: pq_ops.encode_pq.lower(
            S((ivf_index.BULK_ROWS, D), jnp.float32), books),
    }[step]()
    _report(step, lowered.compile())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_brute_force_search_compiles(one_chip, widths, dtype):
    """The UNINDEXED fall-back and the shadow sampler's exact scan, at
    the store's default dtype and at bfloat16."""
    S, n = _shapes(one_chip), widths["n_store"]
    compiled = brute_force_search.lower(
        S((64, D), dtype), S((n, D), dtype), S((n,), jnp.bool_),
        widths["fetch_k"], L2, S((n,), jnp.float32)).compile()
    _report(f"brute_force_search[{jnp.dtype(dtype).name}]", compiled)


def _raw_shape(n_store, d):
    """The sharded raw store as `RawVectorStore.device_buffer_sharded`
    places it: `row_pack(d)` rows a device row."""
    pack = row_pack(d)
    return (n_store // pack, pack * d)


def test_mesh_fused_program_compiles_for_four_chips(topo, one_chip, widths):
    """The mesh-spanning partition's ONE program on a 4-device mesh of
    the described chips — shapes only. Each device holds about a
    quarter of the bytes the single-device program takes as arguments
    (0.678 GB, as test_fused_scan_rerank_compiles prints), and the
    candidate merge is a collective. At 128 dimensions the raw store is
    placed as it is stored, `[n_store, 128]`: `row_pack` 1."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "query"))
    n_mirror = ShardedRowCache(align=512).capacity(mesh, ROWS)
    n_store = ShardedRowCache(align=128).capacity(mesh, ROWS)
    assert _raw_shape(n_store, D) == (n_store, D)

    def S(shape, dt, *spec):
        return jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, P(*spec)))

    fn = sharded._ivf_search_fn(mesh, RERANK, widths["fetch_k"], L2, L2,
                                "int8", 0)
    compiled = fn.lower(
        S((n_mirror, D), jnp.int8, "data", None),
        S((n_mirror,), jnp.float32, "data"),
        S((n_mirror,), jnp.float32, "data"),
        S((n_mirror,), jnp.bool_, "data"),
        S(_raw_shape(n_store, D), jnp.float32, "data", None),
        S((n_store,), jnp.float32, "data"),
        S((64, D), jnp.float32, "query", None)).compile()
    per_device, _ = _report("sharded_ivf_search[4 chips, B=64]", compiled)
    single = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in _fused_args(_shapes(one_chip), 64, widths["n_mirror"],
                             widths["n_store"]))
    assert 0.20 < per_device / single < 0.30, (per_device, single)
    assert "all-gather" in compiled.as_text()
    # a shard's selection sorts no wider than the one-chip program's
    local_n = n_mirror // 4
    assert 0 < _widest_sort_input(compiled, 64) \
        <= perf_model.select_width(RERANK, local_n)


# benchmark/configs/deep10m-mesh4-ivfpq.json: 96-d rows over the 4 chips
# of one host, at the committed row count and at the 10M target
DEEP_D = 96


@pytest.mark.parametrize("rows,b", [(4_000_000, 64), (4_000_000, 256),
                                    (10_000_000, 64)])
def test_deep_mesh_program_compiles_for_four_chips(topo, widths, rows, b):
    """The serving program of `deep10m-mesh4-ivfpq` at the shard sizes
    its placement caches pick: XLA module `jit_sharded_fused_scan_rerank`
    (what the benchmark finds it by on the device trace), both
    collectives, and per chip ONE instruction that writes a
    [B, N/4] f32 score matrix, as the one-chip program since PR 26.

    At 96 dimensions the raw shard is handed in as the store places
    it, `[N/16, 384]`: four rows a 3 x 128-lane device row (`row_pack`),
    which the chip keeps row-major (`{1,0:T(8,128)}`). The rerank gathers
    r device rows a query straight from the parameter and no instruction
    reads or writes the shard: nothing but the score matrix is as large
    as a shard, and the matrix is the program's temp. (Handed in as
    `[N/4, 96]` the chip keeps it column-major, `{0,1:T(8,128)}`, and
    the row gather costs a row-major `copy` of the whole shard in every
    dispatch: 1.39 ms of 4.62 at 1M rows a chip, and twice the temp.)"""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "query"))
    n_mirror = ShardedRowCache(align=512).capacity(mesh, rows)
    n_store = ShardedRowCache(align=128).capacity(mesh, rows)

    def S(shape, dt, *spec):
        return jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, P(*spec)))

    fn = sharded._ivf_search_fn(mesh, RERANK, widths["fetch_k"], L2, L2,
                                "int8", 0)
    compiled = fn.lower(
        S((n_mirror, DEEP_D), jnp.int8, "data", None),
        S((n_mirror,), jnp.float32, "data"),
        S((n_mirror,), jnp.float32, "data"),
        S((n_mirror,), jnp.bool_, "data"),
        S(_raw_shape(n_store, DEEP_D), jnp.float32, "data", None),
        S((n_store,), jnp.float32, "data"),
        S((b, DEEP_D), jnp.float32, "query", None)).compile()
    _, temp = _report(f"sharded_fused_scan_rerank[4 chips, {rows} x "
                      f"{DEEP_D}, B={b}]", compiled)
    text = compiled.as_text()
    assert text.startswith("HloModule jit_sharded_fused_scan_rerank")
    assert "all-gather" in text and "all-reduce" in text
    for scope in ("score", "block_max", "select", "merge", "rerank", "pmax"):
        assert f"shard_map/{scope}/" in text, scope
    local_n = n_mirror // 4
    assert _raw_shape(n_store, DEEP_D) == (n_store // 4, 4 * DEEP_D)
    assert re.search(
        rf"f32\[{n_store // 16},{4 * DEEP_D}\]{{1,0:T\(8,128\)}} parameter\(4\)",
        text), "the raw shard is not row-major as placed"
    # ONE instruction writes the score matrix, and no other f32 result
    # is as large as a shard's raw rows: no copy of args[4] (the int8
    # mirror's prefetch, `copy-start` of args[0], is as many elements)
    written = [name for name in _score_sized(compiled, b, local_n)
               if not name.startswith("copy-")]
    assert len(written) == 1, written
    shard_sized = _score_sized(compiled, n_store // 4, DEEP_D, "f32")
    assert set(shard_sized) <= set(written), shard_sized
    matrix = perf_model.scan_peak_bytes(b, local_n)
    assert matrix <= temp < 1.25 * matrix, (temp, matrix)
    # a shard's selection: the block maxima or the 16 r group maxima
    assert 0 < _widest_sort_input(compiled, b) \
        <= perf_model.select_width(RERANK, local_n)
