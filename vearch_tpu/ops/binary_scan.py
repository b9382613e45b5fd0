"""Packed 1-bit stage-0 scan + progressive three-stage refinement.

The capacity tier below int4 (ROADMAP item 2; reference:
index/impl/gamma_index_ivfrabitq.cc wrapping faiss RaBitQ — estimator
scan over 1-bit codes, then rerank). A row quantizes to its sign bits
plus a per-row magnitude scale (the RaBitQ estimator's first-order
form): row ~= scale * sign(row), stored as a packed bit plane of
`ceil(d/8)` bytes — 8x denser than the int8 mirror's row payload, the
representation that fits billion-scale corpora in HBM.

TPU-native scoring (same departure from the reference as ops/ivf.py's
ADC note): no XOR/popcount loops — those lower to VPU-serial scalar
ops. The kernel unpacks bit planes to ±1 bf16 tiles and feeds one MXU
matmul:  q . (scale * sign(row)) = scale * (q . (2*bits - 1)).
The unpack is transient work the matmul absorbs (exactly like
ops/ivf.py unpack_int4); only the packed planes are HBM-resident.

Progressive refinement chains three representations of the SAME rows:

    stage 0  binary scan over the whole partition      -> top r0
    stage 1  int8/int4 mirror rescore of the r0 rows   -> top r1
    stage 2  exact rerank against the raw base         -> top k

For a RAM store all three stages fuse into ONE jitted program
(`binary_refine_rerank`); a disk store runs stages 0-1 on device
(`binary_refine_candidates`) and gathers stage-2 rows through the mmap
+ readahead path (index/_store_paths.rerank_against_store), exactly
like the int8 disk path. Byte/footprint models live in
ops/perf_model.py (binary_plane_bytes / binary_footprint_bytes); the
dispatch rows are DOCUMENTED_DISPATCHES["ivfrabitq_three_stage*"].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from vearch_tpu.engine.types import MetricType
from vearch_tpu.ops.distance import sqnorms
from vearch_tpu.ops.ivf import NEG_INF, _select_topk, gather_rows, unpack_int4
from vearch_tpu.ops.perf_model import register_jit
from vearch_tpu.tools import lockcheck


def pack_sign_rows(
    rows: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack float rows to sign-bit planes with per-row scale/offset.

    Returns (planes [n, ceil(d/8)] uint8, scale [n] f32, vsq [n] f32)
    where the stored approximation is ``scale * (2*bit - 1)`` per dim
    and vsq = ||approx||^2 = d * scale^2 (sign^2 == 1) — the offset
    term of the L2 score decomposition, so the scan kernel needs no
    extra per-row column beyond (scale, vsq). Dimensions pad up to a
    byte boundary with 0-bits; queries pad with zeros, so pad dims
    contribute nothing to the dot product.
    """
    rows = np.asarray(rows, dtype=np.float32)
    d = rows.shape[1]
    scale = np.maximum(
        np.abs(rows).mean(axis=1), 1e-12
    ).astype(np.float32)
    planes = np.packbits(rows > 0.0, axis=1)  # MSB-first, byte-padded
    vsq = (float(d) * scale * scale).astype(np.float32)
    return planes, scale, vsq


def unpack_bits_pm1(planes: jax.Array) -> jax.Array:
    """[N, d/8] uint8 bit planes -> [N, d] bf16 values in {-1, +1}.

    Layout contract (pack_sign_rows / np.packbits default): bit 7 (MSB)
    of byte j is dimension 8*j — two cheap vector ops and a reshape
    that XLA fuses into the consuming matmul, no per-element loops.
    """
    n, nb = planes.shape
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    bits = (planes[:, :, None] >> shifts) & jnp.uint8(1)
    return bits.reshape(n, nb * 8).astype(jnp.bfloat16) * 2 - 1


def _pad_queries(queries: jax.Array, d_pad: int) -> jax.Array:
    """Zero-pad [B, d] queries to the bit plane's byte-padded width."""
    d = queries.shape[1]
    if d == d_pad:
        return queries
    return jnp.pad(queries, ((0, 0), (0, d_pad - d)))


def _binary_scores(
    queries: jax.Array,    # [B, d] f32
    planes: jax.Array,     # [N_pad, d/8] uint8
    row_scale: jax.Array,  # [N_pad] f32
    row_vsq: jax.Array,    # [N_pad] f32
    valid: jax.Array,      # [N_pad] bool
    metric: MetricType,
) -> jax.Array:
    # named scopes (as ops/ivf.py's): the stage in every operation's
    # op_name, free at run time and no part of the compile cache's key
    with jax.named_scope("unpack"):
        signs = unpack_bits_pm1(planes)  # [N, d_pad] bf16 (transient)
    with jax.named_scope("stage0_score"):
        qp = _pad_queries(queries, signs.shape[1])
        dots = jax.lax.dot_general(
            qp.astype(jnp.bfloat16), signs, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * row_scale[None, :]
        if metric is MetricType.L2:
            scores = -(sqnorms(queries)[:, None] - 2.0 * dots
                       + row_vsq[None, :])
        else:
            scores = dots
        return jnp.where(valid[None, :], scores, NEG_INF)


@functools.partial(jax.jit, static_argnames=("r", "metric"))
def binary_scan_candidates(
    queries: jax.Array,    # [B, d] f32
    planes: jax.Array,     # [N_pad, d/8] uint8 packed sign planes
    row_scale: jax.Array,  # [N_pad] f32 per-row magnitude scale
    row_vsq: jax.Array,    # [N_pad] f32 ||approx||^2 (= d * scale^2)
    valid: jax.Array,      # [N_pad] bool
    r: int,
    metric: MetricType = MetricType.L2,
) -> tuple[jax.Array, jax.Array]:
    """Stage-0 binary full scan: one unpack+matmul + fused top-r.

    Scores are the RaBitQ-style first-order estimate — selection-grade,
    not ranking-grade; downstream stages restore ordering. Shares the
    block-max selection machinery with the int8 scan."""
    scores = _binary_scores(queries, planes, row_scale, row_vsq, valid,
                            metric)
    with jax.named_scope("stage0_select"):
        return _select_topk(scores, r)


def _mirror_rescore(
    queries: jax.Array,   # [B, d] f32
    cand_i: jax.Array,    # [B, r0] i32 (-1 padding)
    approx8: jax.Array,   # [N_pad, d] int8 / [N_pad, d/2] packed int4
    m_scale: jax.Array,   # [N_pad] f32
    m_vsq: jax.Array,     # [N_pad] f32
    r1: int,
    metric: MetricType,
    storage: str,
) -> tuple[jax.Array, jax.Array]:
    """Stage 1: rescore the stage-0 candidates against the int8/int4
    mirror rows (gather + batched matvec) and keep the top r1. The
    payload may come as placed for a gather, `[N_pad / pack, pack * w]`
    (`Int8Mirror.flush(packed=True)`, ops/ivf.py `gather_rows`)."""
    with jax.named_scope("stage1_rescore"):
        d = queries.shape[1]
        safe = jnp.clip(cand_i, 0, m_scale.shape[0] - 1)
        rows = gather_rows(
            approx8, safe, d if storage == "int8" else d // 2)  # [B, r0, w]
        vals = rows.astype(jnp.bfloat16) if storage == "int8" \
            else unpack_int4(rows)
        dots = jax.lax.dot_general(
            queries.astype(jnp.bfloat16), vals,
            (((1,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * m_scale[safe]
        if metric is MetricType.L2:
            scores = -(sqnorms(queries)[:, None] - 2.0 * dots
                       + m_vsq[safe])
        else:
            scores = dots
        scores = jnp.where(cand_i >= 0, scores, NEG_INF)
        r1 = min(r1, scores.shape[1])
        top_s, pos = jax.lax.top_k(scores, r1)
        ids = jnp.take_along_axis(cand_i, pos, axis=1)
        return top_s, jnp.where(jnp.isfinite(top_s), ids, -1)


@functools.partial(
    jax.jit, static_argnames=("r0", "r1", "metric", "storage")
)
def binary_refine_candidates(
    queries: jax.Array,    # [B, d] f32
    planes: jax.Array,     # [N_pad, d/8] uint8
    row_scale: jax.Array,  # [N_pad] f32
    row_vsq: jax.Array,    # [N_pad] f32
    approx8: jax.Array,    # [N_pad, d] int8 / [N_pad, d/2] int4-packed
    m_scale: jax.Array,    # [N_pad] f32 mirror dequant scale
    m_vsq: jax.Array,      # [N_pad] f32 mirror ||approx||^2
    valid: jax.Array,      # [N_pad] bool
    r0: int,
    r1: int,
    metric: MetricType = MetricType.L2,
    storage: str = "int8",
) -> tuple[jax.Array, jax.Array]:
    """Stages 0+1 as ONE program: binary scan -> top r0 -> int8/int4
    mirror rescore -> top r1. The disk-store entry point: the returned
    candidates feed a host mmap gather + exact_rerank_gathered
    (index/_store_paths.rerank_against_store), the same stage-2 shape
    the int8 disk path already pays."""
    _, cand_i = binary_scan_candidates(
        queries, planes, row_scale, row_vsq, valid, r0, metric
    )
    return _mirror_rescore(
        queries, cand_i, approx8, m_scale, m_vsq, r1, metric, storage
    )


@functools.partial(
    jax.jit,
    static_argnames=("r0", "r1", "k", "scan_metric", "rerank_metric",
                     "storage"),
)
def binary_refine_rerank(
    queries: jax.Array,      # [B, d] f32
    planes: jax.Array,       # [N_pad, d/8] uint8
    row_scale: jax.Array,    # [N_pad] f32
    row_vsq: jax.Array,      # [N_pad] f32
    approx8: jax.Array,      # [N_pad, d] int8 / [N_pad, d/2] int4-packed,
                             # or [N_pad / pack, pack * w] (gather_rows)
    m_scale: jax.Array,      # [N_pad] f32
    m_vsq: jax.Array,        # [N_pad] f32
    valid: jax.Array,        # [N_pad] bool
    base: jax.Array,         # [capacity, d] raw store buffer, or
                             # [capacity / pack, pack * d] (gather_rows)
    base_sqnorm: jax.Array,  # [capacity] f32
    r0: int,
    r1: int,
    k: int,
    scan_metric: MetricType = MetricType.L2,
    rerank_metric: MetricType = MetricType.L2,
    storage: str = "int8",
) -> tuple[jax.Array, jax.Array]:
    """The fused three-stage program: binary scan -> int8/int4 rescore
    -> exact rerank, ONE dispatch for a RAM store (same rationale as
    ops/ivf.py int8_scan_rerank — every extra dispatch pays launch
    latency, and the [B, r0]/[B, r1] candidate sets never leave the
    device). Only the final [B, k] pair is fetched."""
    from vearch_tpu.ops.ivf import exact_rerank

    _, cand_i = binary_refine_candidates(
        queries, planes, row_scale, row_vsq, approx8, m_scale, m_vsq,
        valid, r0, r1, scan_metric, storage,
    )
    return exact_rerank(queries.astype(base.dtype), cand_i, base,
                        base_sqnorm, k, rerank_metric)


# -- per-stage serving counters ----------------------------------------------
#
# Process-wide totals of three-stage serving work, rendered by the PS
# as zero-filled fixed-label metrics (vearch_ps_refine_searches_total /
# vearch_ps_refine_stage_rows_total) — fixed topology from the first
# scrape, so the cardinality soak stays flat while traffic warms the
# path mid-soak. Same single-module accumulator pattern as
# perf_model's h2d byte counter.

#: serving shapes of the three-stage chain (fixed metric label set)
REFINE_PATHS: tuple[str, ...] = ("fused", "disk", "mesh")
#: refinement stages (fixed metric label set)
REFINE_STAGES: tuple[str, ...] = ("binary", "int8", "exact")

_stage_lock = lockcheck.make_lock("binary_refine_stats")
_refine_searches: dict[str, int] = {p: 0 for p in REFINE_PATHS}
_refine_stage_rows: dict[str, int] = {s: 0 for s in REFINE_STAGES}


def note_refine_search(path: str, n_rows: int, r0: int, r1: int,
                       k: int, batch: int) -> None:
    """Account one three-stage search: serving shape + rows each stage
    scored (stage 0 scans the partition, stage 1 rescores r0, stage 2
    reranks r1 — all times the query batch)."""
    with _stage_lock:
        _refine_searches[path] = _refine_searches.get(path, 0) + 1
        _refine_stage_rows["binary"] += int(n_rows) * int(batch)
        _refine_stage_rows["int8"] += int(r0) * int(batch)
        _refine_stage_rows["exact"] += int(r1) * int(batch)


def refine_search_counts() -> dict[str, int]:
    with _stage_lock:
        return dict(_refine_searches)


def refine_stage_rows() -> dict[str, int]:
    with _stage_lock:
        return dict(_refine_stage_rows)


# compiled-program tracking (ops/perf_model.py): same rebind idiom as
# ops/ivf.py — the module globals become observing proxies so the
# compile-audit flight recorder sees cache growth on live calls.
for _name, _fn in (
    ("binary.scan_candidates", binary_scan_candidates),
    ("binary.refine_candidates", binary_refine_candidates),
    ("binary.refine_rerank", binary_refine_rerank),
):
    globals()[_name.split(".", 1)[1]] = register_jit(_name, _fn)
