"""IVF probe-scan search kernels.

TPU-native re-design of the reference's IVF list scanning (reference:
index/impl/gamma_index_ivfflat.cc:198, gamma_index_ivfpq.h:1258 — there a
per-query CPU loop over inverted lists; here one jit'd program per query
batch). Layout contract (built by index/ivf.py on publish):

    centroids    [nlist, d]       coarse quantizer
    bucket_ids   [nlist, cap] i32 docid per slot, -1 = padding
    bucket_vecs  [nlist, cap, d]  (IVFFLAT) vectors grouped by cluster
    bucket_codes [nlist, cap, m]  (IVFPQ) uint8 PQ codes of residuals

Search structure: coarse top-nprobe as one matmul + top_k, then a
`lax.scan` over probe ranks. Each step gathers one bucket row per query
([B, cap, ...] — contiguous row DMA, the gather XLA handles well), scores
it (matvec batch on MXU for IVFFLAT; LUT gather for IVFPQ), masks
padding/deleted slots, and folds into a running [B, r] top-k via
concat + top_k. Candidates then get an exact rerank against the raw
device buffer — TPU keeps raw vectors resident anyway, so rerank is one
more gather+matmul and buys back the PQ recall loss (the reference's
fine-grained rerank via raw vectors, gamma_index_ivfpq.h).

Everything is static-shaped: nprobe/k/cap are trace-time constants;
per-request nprobe changes recompile once per distinct value (cached).
"""

from __future__ import annotations

import functools
import threading
import time

import jax
import jax.numpy as jnp

from vearch_tpu.engine.types import MetricType
from vearch_tpu.ops.distance import dot_precision, sqnorms
from vearch_tpu.ops.perf_model import BLOCK, GROUP, GROUP_MIN_R, register_jit

NEG_INF = float("-inf")

# Optional dispatch ledger: when a list (or ops/perf_model.PerfLedger)
# is installed here, index call sites append one tag per device-program
# launch. Lets tests prove the fused hot path really is ONE program
# where the unfused path is two (each dispatch pays launch scheduling;
# the count is backend-independent, so the CPU tests gate it). The
# perf-model layer (ops/perf_model.py) aggregates these into the
# CI-asserted DOCUMENTED_DISPATCHES gate.
_dispatch_ledger: list | None = None


def set_dispatch_ledger(ledger: list | None) -> None:
    global _dispatch_ledger
    _dispatch_ledger = ledger


# Optional dispatch observer (obs/accounting installs one): called as
# observer(tag) from the SAME note_dispatch call that feeds the ledger
# and the per-request capture, so per-tenant dispatch counts reconcile
# with the global ledger exactly — same single-slot contract as
# perf_model.set_compile_observer.
_dispatch_observer = None


def set_dispatch_observer(fn) -> None:
    """Install (or clear, with None) the process-wide dispatch observer."""
    global _dispatch_observer
    _dispatch_observer = fn


# Per-request dispatch capture (observability tentpole): a thread-local
# recorder layered on top of the process-global ledger. The engine
# installs one per search so the profile/trace surface can report which
# device programs THIS request launched and roughly how long each took,
# without touching the index call sites (they keep calling
# note_dispatch). A tag's wall window closes at the next note_dispatch
# or at an explicit capture_mark()/end_capture() — on the CPU backend
# the blocking device_get sits inside that window, so the times are
# host-observed per-dispatch costs, not pure kernel times.
_capture_tls = threading.local()


class DispatchCapture:
    __slots__ = ("events", "kernels", "mesh_phases", "tier_phases",
                 "stage_phases", "phases", "rows", "bucket_rows")

    def __init__(self) -> None:
        # tag -> kernel implementation that served it, for the tags
        # whose call site chooses between implementations (the IVFPQ
        # probe scan: "pallas" | "xla") — the profile reports it so a
        # reader can tell which program a dispatch tag really launched
        self.kernels: dict[str, str] = {}
        # [tag, start, end | None, launched | None, rows, bucket_rows,
        # select_width | None], stamps in monotonic seconds — consumers
        # (engine._record_dispatch_trace) anchor to the epoch via
        # utils.mono_us when emitting spans
        self.events: list[list] = []
        # what the engine is about to hand the index: real query rows,
        # and the rows of the shape bucket they were padded to (what the
        # program runs); stamped on every dispatch noted after
        self.rows = self.bucket_rows = 0
        # (name, start_monotonic_s, end_monotonic_s, tags | None)
        # host-side windows of the mesh serving path (shard placement,
        # mask upload, ...) — replayed by the engine as mesh.{name}
        # phase spans
        self.mesh_phases: list[tuple[str, float, float, dict | None]] = []
        # (name, start_monotonic_s, end_monotonic_s) host-side windows
        # of the tiered-storage path (demand fetch, prefetch schedule,
        # pin-set change) — replayed as tier.{name} phase spans
        self.tier_phases: list[tuple[str, float, float]] = []
        # (name, start_monotonic_s, end_monotonic_s) host-side windows
        # of the progressive-refinement path (bit-plane/mirror flush,
        # the fused refine dispatch, the disk stage-2 gather+rerank) —
        # replayed as stage.{name} phase spans
        self.stage_phases: list[tuple[str, float, float]] = []
        # (full span name, start, end, tags | None): windows noted with
        # note_phase, replayed under their own names
        self.phases: list[tuple[str, float, float, dict | None]] = []

    def note(self, tag: str, kernel: str | None = None,
             select_width: int | None = None) -> None:
        now = time.monotonic()
        if self.events and self.events[-1][2] is None:
            self.events[-1][2] = now
        self.events.append([tag, now, None, None, self.rows,
                            self.bucket_rows, select_width])
        if kernel is not None:
            self.kernels[tag] = kernel

    def launched(self) -> None:
        """The jitted call of the open dispatch has returned: its
        program is enqueued and its query uploaded. What is left of the
        window, up to jax.device_get, is waiting for the device."""
        if self.events and self.events[-1][3] is None:
            self.events[-1][3] = time.monotonic()

    def mark(self) -> None:
        """Close the open dispatch window (call when device work for the
        current index.search has completed)."""
        if self.events and self.events[-1][2] is None:
            self.events[-1][2] = time.monotonic()

    @property
    def tags(self) -> list[str]:
        return [e[0] for e in self.events]


def begin_capture() -> DispatchCapture:
    cap = DispatchCapture()
    _capture_tls.capture = cap
    return cap


def capture_mark() -> None:
    cap = getattr(_capture_tls, "capture", None)
    if cap is not None:
        cap.mark()


def capture_launched() -> None:
    cap = getattr(_capture_tls, "capture", None)
    if cap is not None:
        cap.launched()


def end_capture() -> DispatchCapture | None:
    cap = getattr(_capture_tls, "capture", None)
    _capture_tls.capture = None
    if cap is not None:
        cap.mark()
    return cap


def note_dispatch(tag: str, kernel: str | None = None,
                  select_width: int | None = None) -> None:
    """One device-program launch. `select_width`: for a full-scan site,
    `perf_model.select_width` of the program it launches (the scores a
    query that the widest sort of `_select_topk` takes there)."""
    if _dispatch_ledger is not None:
        _dispatch_ledger.append(tag)
    obs = _dispatch_observer
    if obs is not None:
        obs(tag)
    cap = getattr(_capture_tls, "capture", None)
    if cap is not None:
        cap.note(tag, kernel, select_width)


# Optional phase observer (the PS installs one): takes the windows
# note_phase sees when no request's capture is there to carry them, so
# that rare process-level work (the raw store's re-placement on a path
# nobody profiles) still leaves a span. Same single-slot contract as
# the dispatch observer.
_phase_observer = None


def set_phase_observer(fn) -> None:
    global _phase_observer
    _phase_observer = fn


def clear_phase_observer(fn) -> None:
    """Remove `fn` if it is the installed observer (several servers of
    one process each clear only their own)."""
    global _phase_observer
    if _phase_observer == fn:
        _phase_observer = None


def note_phase(name: str, t0: float, t1: float,
               tags: dict | None = None, request_only: bool = False) -> None:
    """Record a host-side window under its full span name: on the
    current request's capture when there is one (the request that paid
    for it; replayed under ps.search), else to the phase observer: a
    rare one (a re-placement, a publish) then still leaves a
    process-level span. `request_only` is for a window every search
    has (the IVF probe phase): with no capture it is dropped."""
    cap = getattr(_capture_tls, "capture", None)
    if cap is not None:
        cap.phases.append((name, t0, t1, tags))
    elif _phase_observer is not None and not request_only:
        _phase_observer(name, t0, t1, tags)


def note_mesh_phase(name: str, t0: float, t1: float,
                    tags: dict | None = None) -> None:
    """Record a host-side window of the mesh serving path (per-shard
    placement, mask upload) on the current request's capture — shows up
    as a mesh.{name} phase span next to the kernel.* dispatch spans,
    with `tags` (the place phase: `bytes` uploaded) as the span's."""
    cap = getattr(_capture_tls, "capture", None)
    if cap is not None:
        cap.mesh_phases.append((name, t0, t1, tags))


def note_tier_phase(name: str, t0: float, t1: float) -> None:
    """Record a host-side window of the tiered-storage serving path
    (demand slab fetch, prefetch scheduling, pin-set recompute) on the
    current request's capture — shows up as a tier.{name} phase span
    next to the kernel.* dispatch spans. No-op off the request thread
    (the async prefetch worker has no capture installed)."""
    cap = getattr(_capture_tls, "capture", None)
    if cap is not None:
        cap.tier_phases.append((name, t0, t1))


def note_stage_phase(name: str, t0: float, t1: float) -> None:
    """Record a host-side window of the progressive-refinement serving
    path (index/binary.py three-stage chain) on the current request's
    capture — shows up as a stage.{name} phase span next to the
    kernel.* dispatch spans. No-op without an installed capture."""
    cap = getattr(_capture_tls, "capture", None)
    if cap is not None:
        cap.stage_phases.append((name, t0, t1))


def _coarse_probes(
    queries: jax.Array, centroids: jax.Array, nprobe: int
) -> jax.Array:
    """Top-nprobe cluster ids per query [B, nprobe]."""
    dots = jax.lax.dot_general(
        queries, centroids, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    # coarse assignment is L2 geometry for every metric (IP/cosine data is
    # normalized upstream, so nearest-centroid is still the right probe)
    scores = 2.0 * dots - sqnorms(centroids)[None, :]
    _, probes = jax.lax.top_k(scores, nprobe)
    return probes


def _fold_topk(
    best: tuple[jax.Array, jax.Array],
    scores: jax.Array,
    ids: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Fold a new [B, c] candidate block into the running [B, r] top list."""
    best_s, best_i = best
    s_cat = jnp.concatenate([best_s, scores], axis=1)
    i_cat = jnp.concatenate([best_i, ids], axis=1)
    top_s, pos = jax.lax.top_k(s_cat, best_s.shape[1])
    return top_s, jnp.take_along_axis(i_cat, pos, axis=1)


#: the widest slice one step of the IVFFLAT probe scan gathers per
#: query. Up to 1 MiB (2048 rows of 128 f32) the TPU compiler keeps the
#: step's [B, rows, d] gather as one operation and hands it to the
#: product without a buffer in HBM; past it, it cuts the gather into
#: column slices of the WHOLE [nlist, cap, d] table and copies each in
#: every step (at cap 10368: 4.7 GB of temp beside a 5.4 GB table;
#: tests/test_chip_compile.py holds the program to this)
PROBE_SLICE_BYTES = 1 << 20


def probe_tile(cap: int, row_bytes: int) -> int:
    """Rows of a list that one scan step gathers: all `cap` of them
    while they fit PROBE_SLICE_BYTES, else the largest divisor of `cap`
    that does (a table published by index/ivf.py has `cap` rounded up
    to a multiple of `probe_tile_rows`, so that is the divisor)."""
    limit = max(PROBE_SLICE_BYTES // row_bytes, 1)
    if cap <= limit:
        return cap
    return next(t for t in range(limit, 0, -1) if cap % t == 0)


def probe_tile_rows(row_bytes: int) -> int:
    """The tile a publish rounds a long list's `cap` up to: the most
    rows inside PROBE_SLICE_BYTES, in whole 128-row blocks."""
    return max(PROBE_SLICE_BYTES // row_bytes // 128 * 128, 128)


@jax.jit
def bucket_sqnorms(bucket_vecs: jax.Array) -> jax.Array:
    """[nlist, cap] squared norms of a published [nlist, cap, d] table,
    as one fused multiply-and-reduce: taken eagerly (`sqnorms`), the
    squares are a second table-sized array on the device for the length
    of the publish (8.7 GB at peak beside a 4.4 GB table)."""
    return sqnorms(bucket_vecs)


@functools.partial(jax.jit, static_argnames=("nprobe", "r", "metric"))
def ivfflat_candidates(
    queries: jax.Array,      # [B, d] (store dtype)
    centroids: jax.Array,    # [nlist, d] f32
    bucket_vecs: jax.Array,  # [nlist, cap, d] store dtype
    bucket_sqnorm: jax.Array,  # [nlist, cap] f32
    bucket_ids: jax.Array,   # [nlist, cap] i32
    bucket_ok: jax.Array,    # [nlist, cap] bool (slot-major, as the table)
    nprobe: int,
    r: int,
    metric: MetricType = MetricType.L2,
    probes: jax.Array | None = None,  # [B, nprobe] i32 (precomputed)
) -> tuple[jax.Array, jax.Array]:
    """Scan nprobe buckets per query; return top-r (scores, docids).

    `probes` overrides the in-kernel matmul selection — the HNSW coarse
    quantizer computes them on host (quantizer_type=hnsw).

    `bucket_ok` is the validity mask in the table's own order: true
    where the slot holds a row that is alive and passes the request's
    filter, false on padding. A step reads it as it reads `bucket_ids`,
    one row a query: looked up by docid (`valid[ids]`) the same 131,072
    elements a step were 77 % of the program on the chip (PERF.md
    section 6, PR 33).

    A list longer than `probe_tile` rows is scanned in that many-row
    tiles, one scan step each: the table is read as
    [nlist * tiles, tile, d] (a view: `cap` is a multiple of the tile)
    and a step gathers [B, tile, d]. Every slot of every probed list is
    scored and folded either way; the stages carry `jax.named_scope`s
    (`coarse`, `gather`, `score`, `fold`), as the full-scan programs'
    do."""
    b = queries.shape[0]
    if probes is None:
        with jax.named_scope("coarse"):
            probes = _coarse_probes(
                queries.astype(jnp.float32), centroids, nprobe
            )  # [B, nprobe]
    nprobe = int(probes.shape[1])
    q_sq = sqnorms(queries)  # [B]
    nlist, cap, d = bucket_vecs.shape
    tile = probe_tile(cap, d * bucket_vecs.dtype.itemsize)
    tiles = cap // tile
    bucket_vecs = bucket_vecs.reshape(nlist * tiles, tile, d)
    bucket_ids = bucket_ids.reshape(nlist * tiles, tile)
    bucket_sqnorm = bucket_sqnorm.reshape(nlist * tiles, tile)
    bucket_ok = bucket_ok.reshape(nlist * tiles, tile)

    init = (
        jnp.full((b, r), NEG_INF, jnp.float32),
        jnp.full((b, r), -1, jnp.int32),
    )

    def step(best, s):
        with jax.named_scope("gather"):
            c = probes[:, s // tiles]  # [B]
            # c == -1 marks a padded probe slot (host HNSW selection
            # came up short): scan cell 0 for shape but mask every hit —
            # scanning a real cell twice would DUPLICATE its docids in
            # the top-k
            cell_ok = c >= 0
            c = jnp.maximum(c, 0) * tiles + s % tiles
            vecs = bucket_vecs[c]  # [B, tile, d]
            ids = bucket_ids[c]  # [B, tile]
            vsq = bucket_sqnorm[c]  # [B, tile]
            ok = bucket_ok[c] & cell_ok[:, None]  # [B, tile]
        with jax.named_scope("score"):
            dots = jax.lax.dot_general(
                queries, vecs, (((1,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
                precision=dot_precision(queries, vecs),
            )  # [B, tile]
            if metric is MetricType.L2:
                scores = -(q_sq[:, None] - 2.0 * dots + vsq)
            else:
                scores = dots
            scores = jnp.where(ok, scores, NEG_INF)
        with jax.named_scope("fold"):
            return _fold_topk(best, scores, ids), None

    (best_s, best_i), _ = jax.lax.scan(
        step, init, jnp.arange(nprobe * tiles))
    # masked slots keep -inf scores; null their ids so rerank skips them
    return best_s, jnp.where(jnp.isfinite(best_s), best_i, -1)


@functools.partial(jax.jit, static_argnames=("nprobe", "r", "metric"))
def ivfpq_candidates(
    queries: jax.Array,        # [B, d] f32
    centroids: jax.Array,      # [nlist, d] f32
    bucket_resid8: jax.Array,  # [nlist, cap, d] int8 (quantized PQ-decoded residuals)
    bucket_scale: jax.Array,   # [nlist] f32 per-cluster dequant scale
    bucket_vsq: jax.Array,     # [nlist, cap] f32 ||approx vector||^2
    bucket_ids: jax.Array,     # [nlist, cap] i32
    valid: jax.Array,          # [n_pad] bool
    nprobe: int,
    r: int,
    metric: MetricType = MetricType.L2,
    probes: jax.Array | None = None,  # [B, nprobe] i32 (precomputed)
) -> tuple[jax.Array, jax.Array]:
    """MXU-native IVFPQ scan.

    Design note (the one real departure from the reference's ADC): faiss's
    per-query LUT gather is a CPU-cache trick — on TPU it lowers to ~1e8
    scalar VPU gathers per batch and runs ~1000x slower than matmul
    (measured: 31s/batch at SIFT1M scale). The TPU-native formulation
    (cf. ScaNN's accelerator backends) decodes the PQ codes ONCE at
    publish time into int8-quantized residuals and scores buckets with an
    int8->bf16 matmul, which the MXU eats. PQ (m x nbits) remains the
    quantizer — recall characteristics match ADC; int8 is storage of the
    decoded approximation (quantization error ~1/254 of residual range,
    far below PQ error).

    Score decomposition per probed cluster c with approx vector
    v = cent_c + s_c * r8:
        q.v      = q.cent_c + s_c * (q.r8)
        L2 score = -(||q||^2 - 2 q.v + ||v||^2)   (||v||^2 precomputed)
        IP score = q.v
    """
    b = queries.shape[0]
    if probes is None:
        probes = _coarse_probes(queries, centroids, nprobe)  # [B, nprobe]
    nprobe = int(probes.shape[1])
    q_sq = sqnorms(queries)
    qb = queries.astype(jnp.bfloat16)

    init = (
        jnp.full((b, r), NEG_INF, jnp.float32),
        jnp.full((b, r), -1, jnp.int32),
    )

    def step(best, pr):
        c = probes[:, pr]  # [B]
        # padded probe slots (c == -1) scan cell 0 fully masked — see
        # the ivfflat step for why duplicates would otherwise leak
        cell_ok = c >= 0
        c = jnp.maximum(c, 0)
        cent = centroids[c]  # [B, d] f32
        resid8 = bucket_resid8[c]  # [B, cap, d] int8
        ids = bucket_ids[c]  # [B, cap]
        vsq = bucket_vsq[c]  # [B, cap]
        dot8 = jax.lax.dot_general(
            qb, resid8.astype(jnp.bfloat16), (((1,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [B, cap]
        qc = jnp.sum(queries * cent, axis=1)  # [B]
        dots = qc[:, None] + bucket_scale[c][:, None] * dot8
        if metric is MetricType.L2:
            scores = -(q_sq[:, None] - 2.0 * dots + vsq)
        else:
            scores = dots
        ok = (ids >= 0) & valid[jnp.maximum(ids, 0)] & cell_ok[:, None]
        scores = jnp.where(ok, scores, NEG_INF)
        return _fold_topk(best, scores, ids), None

    (best_s, best_i), _ = jax.lax.scan(step, init, jnp.arange(nprobe))
    return best_s, jnp.where(jnp.isfinite(best_s), best_i, -1)


@functools.partial(jax.jit, static_argnames=("r", "metric"))
def int8_scan_candidates(
    queries: jax.Array,    # [B, d] f32
    approx8: jax.Array,    # [N_pad, d] int8 docid-ordered quantized vectors
    row_scale: jax.Array,  # [N_pad] f32 per-row dequant scale
    row_vsq: jax.Array,    # [N_pad] f32 ||approx||^2
    valid: jax.Array,      # [N_pad] bool
    r: int,
    metric: MetricType = MetricType.L2,
) -> tuple[jax.Array, jax.Array]:
    """Compressed full scan: one [B, d] x [d, N] int8 matmul + top-r.

    The default IVFPQ scan path: one big MXU matmul over the int8
    mirror, which reads 4x less HBM than the bf16 raw buffer. The
    [B, N] f32 score matrix is written once and `_select_topk` picks
    the r candidates from it without copying it; the exact rerank
    stage restores the user-facing order and scores.
    """
    # named scopes (here, in _select_topk and in exact_rerank) put the
    # stage into every operation's op_name, which is what the profiler's
    # viewer groups by; they cost nothing at run time and are no part of
    # the compilation cache's key
    with jax.named_scope("score"):
        dots8 = jax.lax.dot_general(
            queries.astype(jnp.bfloat16), approx8.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [B, N]
        dots = dots8 * row_scale[None, :]
        if metric is MetricType.L2:
            scores = -(sqnorms(queries)[:, None] - 2.0 * dots
                       + row_vsq[None, :])
        else:
            scores = dots
        scores = jnp.where(valid[None, :], scores, NEG_INF)
    return _select_topk(scores, r)


def _select_topk(scores: jax.Array, r: int) -> tuple[jax.Array, jax.Array]:
    """Top-r of every row of a [B, N] f32 score matrix, shared by every
    full scan (int8, int4, binary, the mesh programs): (scores, ids),
    ids of masked slots -1.

    One `lax.top_k` over the row is a multi-pass sort of the whole
    matrix: it serves below 4 * max(r, 128) blocks (65,536 rows at
    r <= 128) and an N that is no multiple of BLOCK; from there on
    `_blocked_topk` does (rows of equal score may order otherwise).
    `perf_model.select_width` says how wide the widest sort is."""
    n_pad = scores.shape[1]
    r = min(r, n_pad)
    if n_pad % BLOCK == 0 and n_pad // BLOCK >= 4 * max(r, 128):
        top_s, ids = _blocked_topk(scores, r)
    else:
        top_s, ids = jax.lax.top_k(scores, r)
    # candidates that are really masked slots (filtered/deleted/padding)
    # carry -inf scores — mark their ids -1 so downstream rerank cannot
    # resurrect them with genuine similarity scores
    return top_s, jnp.where(jnp.isfinite(top_s), ids, -1)


def _pick(table: jax.Array, col: jax.Array) -> jax.Array:
    """`table[b, col[b, i]]` for a [B, n] i32 table and [B, r] columns,
    by compare-and-sum over the n columns: an element gather of B * r
    entries costs the chip ten times this loop (0.17 ms against 0.01 at
    B=64)."""
    hit = col[:, :, None] == jnp.arange(table.shape[1])
    return jnp.sum(jnp.where(hit, table[:, None, :], 0), axis=2)


def _blocked_topk(scores: jax.Array, r: int) -> tuple[jax.Array, jax.Array]:
    """Exact top-r of a [B, N] f32 matrix whose N is a multiple of
    BLOCK, r <= N, by levels of maxima:

    1. `block_max`: the maximum of every BLOCK-wide block of a row, in
       f32, and the r blocks with the largest maxima, gathered.
    2. `select`, from r = GROUP_MIN_R on: the r * BLOCK gathered scores
       of a row in groups of GROUP (`_grouped_topk`), the r groups with
       the largest maxima brought together, and `lax.top_k` over their
       GROUP * r scores. Below it, `lax.top_k` over the r * BLOCK.

    The result is the exact top-r of the row at either level: a score
    among the r largest has a group maximum no smaller than itself, so
    fewer than r groups can rank before its group, whatever the groups
    are. No sort of the program is wider than
    max(N / BLOCK, r * BLOCK / GROUP) scores a row (16 r).

    LAYOUT. On the TPU the score fusion writes [B, N] f32 in tiles of 8
    rows x 128 lanes, physically [B/8, N/128, 8, 128]. The first level
    reads it through exactly that 4-d view, so the transpose below is a
    bitcast and the matrix is never copied: the maxima are a lane
    reduction, and a gathered block is one 128-lane row of one tile.
    Any other blocked view ([B, N/BLOCK, BLOCK], block-major, bf16) is
    a physical relayout of the whole matrix: two of them were 42 % of
    this program's device time at B=64, N=1M (PERF.md section 6, PR 26).
    A B that is no multiple of 8 (no bucket the engine dispatches)
    takes the same code with 1-row tiles and pays for its relayout.
    """
    b, n_pad = scores.shape
    nblk = n_pad // BLOCK
    nb = min(r, nblk)
    sub = 8 if b % 8 == 0 else 1
    tiles = scores.reshape(b // sub, sub, nblk, BLOCK).transpose(
        0, 2, 1, 3)  # [B/sub, nblk, sub, BLOCK]
    with jax.named_scope("block_max"):
        bmax = jnp.max(tiles, axis=3).transpose(0, 2, 1).reshape(
            b, nblk)
    with jax.named_scope("select"):
        _, top_blocks = jax.lax.top_k(bmax, nb)  # [B, nb]
        row = jnp.arange(b)[:, None]
        gathered = tiles[row // sub, top_blocks, row % sub, :]
        if nb == r and r >= GROUP_MIN_R and r % GROUP == 0:
            top_s, slot, lane = _grouped_topk(gathered, r)
            ids = _pick(top_blocks, slot) * BLOCK + lane
        else:
            top_s, pos = jax.lax.top_k(gathered.reshape(b, nb * BLOCK), r)
            ids = _pick(top_blocks, pos // BLOCK) * BLOCK + pos % BLOCK
        ids = ids.astype(jnp.int32)
    return top_s, ids


def _grouped_topk(
    gathered: jax.Array, r: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The second level of `_blocked_topk`: top-r of the [B, r, BLOCK]
    gathered blocks of a row as (scores, block slot, lane), each [B, r].

    A group is GROUP consecutive lanes of one gathered block, 16 to a
    block. `top_k` over the 16 r group maxima picks r groups; the block
    of each is gathered once more, from the gathered array (a 128-lane
    row, as the first gather's: the chip has no cheaper unit), its 15
    other groups are masked out, and the last `top_k` runs over the
    GROUP * r scores that are left. Slot and lane come back by
    arithmetic from the chosen group.

    Measured on one TPU v5e, `_select_topk` alone at B=64 (PERF.md
    section 6, PR 35): 2.23 -> 1.28 ms at 1,000,448 x r 256, 3.60 ->
    1.42 at 500,224 x r 512, 1.66 -> 1.02 at r 128, 1.11 -> 0.87 at
    r 64, 0.66 -> 0.62 at r 32: GROUP_MIN_R is 64, the least depth with
    a clear gain. Forms that bring the groups' scores together
    otherwise (8 x 128 tiles gathered and a lane chosen; sublane groups
    read through a transposed copy) cost 0.02 to 0.34 ms more."""
    b = gathered.shape[0]
    per = BLOCK // GROUP  # groups of one block
    gmax = jnp.max(gathered.reshape(b, r, per, GROUP), axis=3)
    _, groups = jax.lax.top_k(gmax.reshape(b, r * per), r)  # slot * per + g
    blocks = gathered[jnp.arange(b)[:, None], groups // per]  # [B, r, BLOCK]
    mine = (groups % per)[:, :, None, None] == jnp.arange(per)[:, None]
    members = jnp.max(jnp.where(
        mine, blocks.reshape(b, r, per, GROUP), NEG_INF), axis=2)
    top_s, pos = jax.lax.top_k(members.reshape(b, r * GROUP), r)
    group = _pick(groups, pos // GROUP)
    return top_s, group // per, group % per * GROUP + pos % GROUP


def unpack_int4(packed: jax.Array) -> jax.Array:
    """[N, d/2] uint8 nibble-packed -> [N, d] bf16 signed values.

    Layout contract (index/int8_mirror.py quantize_rows_int4): dims
    [0, d/2) live in the LOW nibble, dims [d/2, d) in the HIGH nibble —
    a concat, not an interleave, so the unpack is two cheap vector ops
    and one concatenate that XLA fuses into the consuming matmul.
    """
    lo = (packed & 0xF).astype(jnp.int8)
    lo = lo - ((lo > 7) * jnp.int8(16))
    hi = (packed >> 4).astype(jnp.int8)
    hi = hi - ((hi > 7) * jnp.int8(16))
    return jnp.concatenate([lo, hi], axis=-1).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("r", "metric"))
def int4_scan_candidates(
    queries: jax.Array,    # [B, d] f32
    packed4: jax.Array,    # [N_pad, d/2] uint8 nibble-packed int4 rows
    row_scale: jax.Array,  # [N_pad] f32 per-row dequant scale
    row_vsq: jax.Array,    # [N_pad] f32 ||approx||^2
    valid: jax.Array,      # [N_pad] bool
    r: int,
    metric: MetricType = MetricType.L2,
) -> tuple[jax.Array, jax.Array]:
    """int4 compressed full scan: the capacity tier of the int8 mirror.

    Halves the RESIDENT HBM footprint of the scan structure (the usual
    rows-per-chip limiter) at ~15-level quantization; the unpack to
    bf16 is transient work the MXU matmul absorbs, and the exact rerank
    stage recovers ordering exactly as it does for int8.
    """
    a = unpack_int4(packed4)  # [N, d] bf16
    dots4 = jax.lax.dot_general(
        queries.astype(jnp.bfloat16), a,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, N]
    dots = dots4 * row_scale[None, :]
    if metric is MetricType.L2:
        scores = -(sqnorms(queries)[:, None] - 2.0 * dots + row_vsq[None, :])
    else:
        scores = dots
    scores = jnp.where(valid[None, :], scores, NEG_INF)
    return _select_topk(scores, r)


@functools.partial(jax.jit, static_argnames=("r", "metric"))
def cached_bucket_scan(
    queries: jax.Array,     # [B, d] f32
    pool8: jax.Array,       # [slots, cap, d] int8 (HBM bucket cache)
    pool_scale: jax.Array,  # [slots, cap] f32 per-row dequant scale
    pool_vsq: jax.Array,    # [slots, cap] f32 ||approx||^2
    pool_ids: jax.Array,    # [slots, cap] i32 docids (-1 padding)
    probe_slots: jax.Array,  # [B, nprobe] i32 cache slot per probe
    valid: jax.Array,       # [n_pad] bool (docid-indexed)
    r: int,
    metric: MetricType = MetricType.L2,
) -> tuple[jax.Array, jax.Array]:
    """Probe scan over the HBM bucket cache (disk-tier search path).

    Identical math to `int8_scan_candidates` restricted to the probed
    slabs: rows are per-row-scaled int8 approximations of FULL vectors
    (not residuals), so score = f(q . row) with no centroid term. The
    slot indirection was resolved on host by HbmBucketCache; the kernel
    only ever sees static shapes [slots, cap, d], so one compile serves
    the whole life of a cache generation.
    """
    b = queries.shape[0]
    nprobe = probe_slots.shape[1]
    q_sq = sqnorms(queries)
    qb = queries.astype(jnp.bfloat16)

    init = (
        jnp.full((b, r), NEG_INF, jnp.float32),
        jnp.full((b, r), -1, jnp.int32),
    )

    def step(best, pr):
        s = probe_slots[:, pr]  # [B]
        # slot -1 marks a probe deferred to another fixed-shape pass
        # (multi-pass resolve when the probe set exceeds cache slots):
        # clamp the gather and mask the whole slab out of the fold
        slot_ok = s >= 0  # [B]
        s = jnp.maximum(s, 0)
        slab8 = pool8[s]  # [B, cap, d]
        ids = pool_ids[s]  # [B, cap]
        vsq = pool_vsq[s]
        dot8 = jax.lax.dot_general(
            qb, slab8.astype(jnp.bfloat16), (((1,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [B, cap]
        dots = pool_scale[s] * dot8
        if metric is MetricType.L2:
            scores = -(q_sq[:, None] - 2.0 * dots + vsq)
        else:
            scores = dots
        ok = (ids >= 0) & valid[jnp.maximum(ids, 0)] & slot_ok[:, None]
        scores = jnp.where(ok, scores, NEG_INF)
        return _fold_topk(best, scores, ids), None

    (best_s, best_i), _ = jax.lax.scan(step, init, jnp.arange(nprobe))
    return best_s, jnp.where(jnp.isfinite(best_s), best_i, -1)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def exact_rerank_gathered(
    queries: jax.Array,    # [B, d] f32
    cand_ids: jax.Array,   # [B, r] i32 (-1 padding)
    cand_vecs: jax.Array,  # [B, r, d] f32 (host-gathered raw rows)
    k: int,
    metric: MetricType = MetricType.L2,
) -> tuple[jax.Array, jax.Array]:
    """Exact rerank when the raw base lives on disk: candidate rows were
    gathered host-side (mmap page faults) and ride up as one [B, r, d]
    blob — the only H2D traffic the disk tier pays per query batch."""
    dots = jax.lax.dot_general(
        queries, cand_vecs, (((1,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=dot_precision(queries, cand_vecs),
    )  # [B, r]
    vsq = jnp.sum(
        cand_vecs.astype(jnp.float32) ** 2, axis=2
    )
    if metric is MetricType.L2:
        scores = -(sqnorms(queries)[:, None] - 2.0 * dots + vsq)
    elif metric is MetricType.COSINE:
        qn = jnp.sqrt(jnp.maximum(sqnorms(queries), 1e-30))[:, None]
        vn = jnp.sqrt(jnp.maximum(vsq, 1e-30))
        scores = dots / (qn * vn)
    else:
        scores = dots
    scores = jnp.where(cand_ids >= 0, scores, NEG_INF)
    k = min(k, scores.shape[1])
    top_s, pos = jax.lax.top_k(scores, k)
    return top_s, jnp.take_along_axis(cand_ids, pos, axis=1)


def gather_rows(b: jax.Array, rows: jax.Array, d: int) -> jax.Array:
    """Logical rows `rows` [...] of a row store as placed for a gather,
    `[n / pack, pack * d]` (parallel/mesh.py `row_pack`; the mesh raw
    slab, and on one chip the raw store and the int8 mirror at a width
    that is no multiple of 128) -> [..., d]: super-row `row // pack` off
    the row-major array, then sub-row `row % pack`, so no instruction
    reads the whole store. `pack` 1, a plain `[n, d]`, is the plain
    gather."""
    pack = b.shape[1] // d
    if pack == 1:
        return b[rows]
    sup = b[rows // pack]  # [..., pack * d]
    sub = (rows % pack)[..., None]
    vecs = sup[..., :d]
    for j in range(1, pack):
        vecs = jnp.where(sub == j, sup[..., j * d:(j + 1) * d], vecs)
    return vecs


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def exact_rerank(
    queries: jax.Array,     # [B, d] (store dtype)
    cand_ids: jax.Array,    # [B, r] i32 (-1 padding)
    base: jax.Array,        # [capacity, d] store dtype (raw vector buffer)
    base_sqnorm: jax.Array,  # [capacity] f32
    k: int,
    metric: MetricType = MetricType.L2,
) -> tuple[jax.Array, jax.Array]:
    """Exact re-scoring of candidate docids against the raw device buffer.

    One row gather + batched matvec; recovers exact ordering (and exact
    user-facing scores) on top of ADC approximations. `base` may be the
    store as placed for a gather, `[capacity / pack, pack * d]`
    (`gather_rows`).
    """
    with jax.named_scope("rerank"):
        safe = jnp.maximum(cand_ids, 0)
        vecs = gather_rows(base, safe, queries.shape[1])  # [B, r, d]
        vsq = base_sqnorm[safe]  # [B, r]
        dots = jax.lax.dot_general(
            queries, vecs, (((1,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=dot_precision(queries, vecs),
        )  # [B, r]
        if metric is MetricType.L2:
            scores = -(sqnorms(queries)[:, None] - 2.0 * dots + vsq)
        elif metric is MetricType.COSINE:
            qn = jnp.sqrt(jnp.maximum(sqnorms(queries), 1e-30))[:, None]
            vn = jnp.sqrt(jnp.maximum(vsq, 1e-30))
            scores = dots / (qn * vn)
        else:
            scores = dots
        scores = jnp.where(cand_ids >= 0, scores, NEG_INF)
        k = min(k, scores.shape[1])
        top_s, pos = jax.lax.top_k(scores, k)
        return top_s, jnp.take_along_axis(cand_ids, pos, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("r", "k", "scan_metric", "rerank_metric", "storage"),
)
def int8_scan_rerank(
    queries: jax.Array,      # [B, d] f32
    approx8: jax.Array,      # [N_pad, d] int8 (or [N_pad, d/2] int4-packed)
    row_scale: jax.Array,    # [N_pad] f32
    row_vsq: jax.Array,      # [N_pad] f32
    valid: jax.Array,        # [N_pad] bool
    base: jax.Array,         # [capacity, d] raw store buffer
    base_sqnorm: jax.Array,  # [capacity] f32
    r: int,
    k: int,
    scan_metric: MetricType = MetricType.L2,
    rerank_metric: MetricType = MetricType.L2,
    storage: str = "int8",
) -> tuple[jax.Array, jax.Array]:
    """Fused compressed scan + exact rerank: ONE device program per
    search instead of two (each dispatch pays launch scheduling; fusing
    also keeps the [B, r] candidate set entirely on device and lets XLA
    schedule the rerank gather against the scan's top-k tail).

    scan_metric is the compressed-domain metric (cosine scans as IP on
    pre-normalized rows); rerank_metric the user-facing one. Only the
    final [B, k] pair ever leaves the device."""
    scan = (int8_scan_candidates if storage == "int8"
            else int4_scan_candidates)
    _, cand_i = scan(queries, approx8, row_scale, row_vsq, valid,
                     r, scan_metric)
    return exact_rerank(queries.astype(base.dtype), cand_i, base,
                        base_sqnorm, k, rerank_metric)


# compiled-program tracking (ops/perf_model.py): every jitted search
# entry point registers here so tests can assert that repeated
# same-shape searches add ZERO new compiled programs — the retrace /
# compile-stall regression gate. The module global is rebound to the
# returned observing proxy so the compile-audit flight recorder sees
# cache growth on live calls (importers bind the proxy too: this runs
# before any `from ... import` of these names executes).
for _name, _fn in (
    ("ivf.ivfflat_candidates", ivfflat_candidates),
    ("ivf.ivfpq_candidates", ivfpq_candidates),
    ("ivf.int8_scan_candidates", int8_scan_candidates),
    ("ivf.int4_scan_candidates", int4_scan_candidates),
    ("ivf.cached_bucket_scan", cached_bucket_scan),
    ("ivf.exact_rerank", exact_rerank),
    ("ivf.exact_rerank_gathered", exact_rerank_gathered),
    ("ivf.int8_scan_rerank", int8_scan_rerank),
):
    globals()[_name.split(".", 1)[1]] = register_jit(_name, _fn)
