"""Hardware-independent count model + regression gates.

Counts that repeat exactly on any backend — device-program launches per
serving path, compiled specialisations, bytes derived from shapes — are
modeled here and asserted in tests/test_perf_gates.py on the CPU
backend, the way recall is gated in CI. They are counts, never speeds:
a time, a rate or a roofline share comes only from a chip run
(`python chip_smoke.py` through the chip tool; PERF.md).

Four layers:

1. `PerfLedger` — drop-in for the plain-list dispatch ledger
   (ops/ivf.py set_dispatch_ledger): call sites append one tag per
   device-program launch; the ledger aggregates per-search counts.
2. jit registry — every jitted search entry point registers itself via
   `register_jit`; `compiled_program_counts()` reads each function's
   live jit-cache size, so a test can assert that repeated same-shape
   searches add ZERO new compiled programs (no silent retrace).
3. bytes-materialized model — peak intermediate HBM bytes of the
   full-scan path, mirroring the real kernel constants (ops/ivf.py
   BLOCK): the ONE [B, N] f32 score matrix the XLA scan materializes.
4. HBM-footprint model — resident device bytes per index type
   (index.device_footprint_bytes() feeds these helpers), the
   rows-per-chip capacity planner.

Everything here is arithmetic over shapes — no device access — so the
gates run identically with and without a TPU.
"""

from __future__ import annotations

import enum
import threading
import time
from typing import Any, Callable

# the selection of ops/ivf.py (`_select_topk` takes them from here)
BLOCK = 128  # its first level: the lanes of one score tile
GROUP = 8  # its second level: lanes of one group of a gathered block
#: the depth from which the second level is taken (`_grouped_topk`)
GROUP_MIN_R = 64

F32 = 4


# -- 1. dispatch ledger ------------------------------------------------------


class PerfLedger:
    """Dispatch ledger with per-search aggregation.

    Compatible with the plain ``list`` contract of
    ops/ivf.py ``set_dispatch_ledger`` (call sites only ever
    ``append(tag)``); adds search boundaries and count summaries on top.
    """

    def __init__(self) -> None:
        self.tags: list[str] = []
        self._marks: list[int] = []

    # list-compat surface used by note_dispatch call sites
    def append(self, tag: str) -> None:
        self.tags.append(tag)

    def __iter__(self):
        return iter(self.tags)

    def __len__(self) -> int:
        return len(self.tags)

    def __eq__(self, other: object) -> bool:
        if isinstance(other, PerfLedger):
            return self.tags == other.tags
        return self.tags == other

    def mark_search(self) -> None:
        """Record a search boundary: tags appended after this call
        belong to the next search."""
        self._marks.append(len(self.tags))

    def per_search(self) -> list[list[str]]:
        """Tags grouped by the mark_search() boundaries."""
        bounds = sorted({0, *self._marks, len(self.tags)})
        return [self.tags[a:b] for a, b in zip(bounds, bounds[1:])]

    def dispatch_count(self) -> int:
        return len(self.tags)

    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for t in self.tags:
            out[t] = out.get(t, 0) + 1
        return out


#: documented device-program launches per engine-level search, by path.
#: tests/test_perf_gates.py asserts the live ledger against this table;
#: docs/PERF.md renders it. A new dispatch on a serving path MUST bump
#: this table in the same PR — that is the regression gate.
DOCUMENTED_DISPATCHES: dict[str, list[str]] = {
    # IVFPQ full-scan, fused scan+rerank (default hot path): ONE program
    "ivfpq_full_fused": ["fused_scan_rerank"],
    # IVFPQ full-scan, two-step: a disk store (its rerank gathers the
    # rows on the host) and SCANN reordering=false, which wants no
    # rerank and stops after "scan"
    "ivfpq_full_unfused": ["scan", "rerank"],
    # IVFPQ probe mode: bucket scan + exact rerank
    "ivfpq_probe": ["probe_scan", "rerank"],
    # IVFFLAT probe scan (scores already exact — no rerank)
    "ivfflat": ["ivfflat_scan"],
    # FLAT exact scan: one fused matmul+topk program
    "flat": ["flat_scan"],
    # served from a result cache (router or PS tier): the whole point
    # is ZERO device programs — the cache perf gates assert an empty
    # ledger for hits and exactly one documented set per coalesced group
    "cache_hit": [],
    # mesh serving (parallel/sharded.py): probe gate + shard scan +
    # all_gather merge + exact rerank + pmax merge, ONE shard_map program
    "ivfpq_mesh_fused": ["sharded_fused_scan_rerank"],
    # mesh serving with no exact rerank wanted (SCANN
    # reordering=false): scan+merge only
    "ivfpq_mesh_scan": ["sharded_scan"],
    # probe regime under the mesh: the fused program gated to the
    # probed coarse cells (nprobe > 0) — past the full-scan cliff a
    # mesh partition no longer falls back to one chip
    "ivfpq_mesh_probe": ["sharded_probe_scan_rerank"],
    # FLAT over the mesh: one fused scan+all_gather+re-top-k program
    "flat_sharded": ["sharded_flat_scan"],
    # progressive three-stage refinement (IVFRABITQ, RAM store): binary
    # stage-0 scan + int8 rescore + exact rerank fused into ONE program
    "ivfrabitq_three_stage": ["binary_refine_rerank"],
    # three-stage over a disk store: stages 0-1 on device, stage-2 rows
    # host-gathered through the mmap + readahead path (same rerank
    # dispatch the int8 disk path pays)
    "ivfrabitq_three_stage_disk": ["binary_refine_scan", "rerank"],
    # three-stage over the mesh: per-shard stages 0-1, one all_gather
    # candidate merge, sharded exact rerank + pmax — ONE shard_map
    # program (parallel/sharded.py sharded_binary_refine)
    "ivfrabitq_mesh_three_stage": ["sharded_binary_refine_rerank"],
}


def path_for_dispatches(tags: list[str]) -> str | None:
    """Reverse lookup: which documented serving path launched exactly
    this dispatch sequence? None when the sequence matches no documented
    path (e.g. a multi-field search concatenates several paths) — the
    profile surface reports that as drift instead of guessing."""
    seq = list(tags)
    for path, doc in DOCUMENTED_DISPATCHES.items():
        if seq == doc:
            return path
    return None


# -- padded shape buckets ----------------------------------------------------
#
# Every distinct (rows, k) pair handed to a jitted search program is a
# separate XLA specialisation: rows changes the traced shape, k is a
# static arg. Free-form traffic therefore compiles an unbounded program
# set and co-batching is limited to exact-(k) matches. The serving path
# instead quantizes BOTH axes to a small declared grid:
#
#   rows    padded up to the next ROW_BUCKETS tier (results sliced
#           back to the caller's row count host-side),
#   fetch-k padded up to the next FETCH_K_TIERS tier (the engine's
#           _shape_results already trims each caller to its own k).
#
# The compiled-program universe per scan path is then at most
# len(ROW_BUCKETS) * len(FETCH_K_TIERS) — warmable in full, which is
# what makes the zero-retrace perf gate assertable — and requests with
# differing k become co-batchable because every member scans at the
# bucket's tier and slices to its own depth on the host. vearch-lint
# VL103 pins serving code to these constants (this module is the single
# source of truth); tests/test_perf_gates.py asserts the dispatch bound.

#: declared row tiers for batched serving dispatches
ROW_BUCKETS: tuple[int, ...] = (8, 64, 256, 1024)
#: declared fetch-k tiers (candidate depth handed to the index)
FETCH_K_TIERS: tuple[int, ...] = (16, 64, 256, 1024)
#: declared recall-estimator depths (obs/quality.py shadow sampling):
#: head correctness, the common serving page, and candidate-set health.
#: Declared here with the other tier grids so VL103 keeps quality code
#: off ad-hoc depth literals.
RECALL_K_TIERS: tuple[int, ...] = (1, 10, 100)


def bucket_rows(b: int) -> int:
    """Smallest declared row tier holding `b` rows. Above the top tier
    returns `b` unchanged — a caller-supplied mega-batch is already one
    dispatch and padding it further would only waste HBM."""
    for t in ROW_BUCKETS:
        if b <= t:
            return t
    return int(b)


def bucket_fetch_k(k: int) -> int:
    """Smallest declared fetch-k tier covering depth `k`; above the top
    tier returns `k` unchanged (out-of-bucket, documented as such)."""
    for t in FETCH_K_TIERS:
        if k <= t:
            return t
    return int(k)


def bucket_program_bound(row_tiers: int | None = None,
                         k_tiers: int | None = None) -> int:
    """Upper bound on compiled specialisations per scan path once both
    axes are quantized: the full declared grid."""
    r = len(ROW_BUCKETS) if row_tiers is None else int(row_tiers)
    k = len(FETCH_K_TIERS) if k_tiers is None else int(k_tiers)
    return r * k


def bucket_dispatch_bound(n_requests: int, bucket_capacity: int) -> int:
    """Max device dispatches a continuous-batching scheduler may issue
    for `n_requests` single-row requests sharing one bucket key:
    ceil(requests / capacity). The perf gate asserts the live ledger
    against this."""
    return -(-int(n_requests) // max(int(bucket_capacity), 1))


def padding_waste_bytes(real_rows: int, padded_rows: int, d: int,
                        itemsize: int = F32) -> int:
    """Query bytes a padded dispatch moves for nobody: the pad rows of
    the [padded_rows, d] query block. The scheduler accumulates this per
    dispatch; the doctor flags sustained waste > 50%."""
    return max(int(padded_rows) - int(real_rows), 0) * int(d) * int(itemsize)


# -- 2. compiled-program tracking -------------------------------------------

_JIT_REGISTRY: dict[str, Any] = {}

# Optional compile observer (the obs/ flight recorder installs one):
# called as observer(program_name, shape_signature, elapsed_ms) whenever
# a *call* of a registered program grew its jit cache — i.e. XLA
# compiled a new specialisation on what should be a warmed path.
_compile_observer: Any = None


def set_compile_observer(fn: Any) -> None:
    """Install (or clear, with None) the process-wide compile observer."""
    global _compile_observer
    _compile_observer = fn


def _sig_of(v: Any) -> str:
    """One arg's contribution to a call signature: dtype+shape for
    array-likes, the VALUE for plain scalars (static args specialise on
    value — two calls differing only in a static ``k`` are different
    programs and must not collapse to the same signature), type name
    for everything else."""
    shp = getattr(v, "shape", None)
    if shp is not None:
        dt = getattr(v, "dtype", None)
        return f"{getattr(dt, 'name', dt)}{tuple(shp)}"
    if isinstance(v, (bool, int, float, str)) or v is None:
        return repr(v)
    if isinstance(v, enum.Enum):
        return str(v)
    return type(v).__name__


def _shape_signature(args: tuple, kwargs: dict) -> str:
    """Compact abstract signature of a call: per-arg dtype+shape for
    array-likes, value for static-able scalars. This is what XLA
    specialises on, so it names the compile cause in flight-recorder
    events."""
    parts = [_sig_of(a) for a in args]
    parts += [f"{k}={_sig_of(kwargs[k])}" for k in sorted(kwargs)]
    return "|".join(parts)


class _ObservedJit:
    """Callable proxy over a registered jit entry point.

    Detects jit-cache growth around each call — the only reliable
    compile signal the public JAX API exposes — and notifies the
    installed observer with the call's shape signature and wall time.
    With no observer installed the call passes straight through; every
    attribute access (``_cache_size``, ``lower``, ...) delegates to the
    wrapped function, so the proxy is drop-in for existing callers.
    """

    __slots__ = ("_vearch_name", "_vearch_fn")

    def __init__(self, name: str, fn: Any):
        self._vearch_name = name
        self._vearch_fn = fn

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        obs = _compile_observer
        fn = self._vearch_fn
        if obs is None:
            return fn(*args, **kwargs)
        try:
            before = int(fn._cache_size())
        except Exception:
            before = -1
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        if before >= 0:
            try:
                grew = int(fn._cache_size()) > before
            except Exception:
                grew = False
            if grew:
                obs(
                    self._vearch_name,
                    _shape_signature(args, kwargs),
                    (time.perf_counter() - t0) * 1000.0,
                )
        return out

    def __getattr__(self, item: str) -> Any:
        return getattr(self._vearch_fn, item)


def register_jit(name: str, fn: Any) -> Any:
    """Register a jitted search entry point for compile tracking.

    Returns an observing proxy of `fn` so modules can write
    ``fn = register_jit("name", jax.jit(...))``; the raw function stays
    in the registry so :func:`compiled_program_counts` reads the jit
    cache directly.
    """
    _JIT_REGISTRY[name] = fn
    return _ObservedJit(name, fn)


def compiled_program_counts() -> dict[str, int]:
    """Live jit-cache entry count per registered search program.

    Each entry is one (shape, static-args) specialisation XLA compiled.
    Stable counts across repeated searches == no retrace on the hot
    path; growth with every request is the compile-stall regression the
    warmup + persistent-cache work exists to prevent.
    """
    out: dict[str, int] = {}
    for name, fn in _JIT_REGISTRY.items():
        try:
            out[name] = int(fn._cache_size())
        except Exception:
            out[name] = -1  # jit internals moved; surface loudly
    return out


def total_compiled_programs() -> int:
    return sum(max(v, 0) for v in compiled_program_counts().values())


# Process-wide host->device transfer accounting. The mesh row caches
# and the engine device_put sites already count their own H2D bytes
# per instance; this accumulator is the cross-instance total the
# device-runtime sampler exports as vearch_ps_h2d_bytes_total. A
# counter (not a gauge over instances) survives engine close/reopen.
_h2d_lock = threading.Lock()
_h2d_bytes_total = 0

# Optional H2D observer (obs/accounting installs one): called with the
# byte count from the SAME note_h2d_bytes call that feeds the process
# total, so per-tenant byte meters reconcile with h2d_bytes_total
# exactly — same single-slot contract as set_compile_observer.
_h2d_observer: Any = None


def set_h2d_observer(fn: Any) -> None:
    """Install (or clear, with None) the process-wide H2D byte observer."""
    global _h2d_observer
    _h2d_observer = fn


def note_h2d_bytes(n: int) -> None:
    """Record `n` bytes copied host->device (call at device_put sites)."""
    global _h2d_bytes_total
    with _h2d_lock:
        _h2d_bytes_total += int(n)
    obs = _h2d_observer
    if obs is not None:
        obs(int(n))


def h2d_bytes_total() -> int:
    with _h2d_lock:
        return _h2d_bytes_total


# -- bytes-over-PCIe model (tiered storage engine) --------------------------
#
# The disk tier's only per-query H2D traffic with a warm cache is ZERO:
# a hit serves entirely from the resident HBM slab pools. A miss pays
# exactly one slab upload — four arrays of fixed shape [cap, ...]:
#
#     int8 rows   cap * d   bytes
#     scale f32   cap * 4
#     vsq   f32   cap * 4
#     docids i32  cap * 4
#
# so slab_bytes(cap, d) = cap * (d + 12), and a resolve with `m` misses
# moves tier_h2d_bytes(m, cap, d) = m * slab_bytes over PCIe (the slot
# index vector rides in the dispatch, not the ledger). HbmBucketCache
# notes the actual uploaded nbytes through note_h2d_bytes, and
# tests/test_perf_gates.py asserts ledger delta == model exactly:
# zero for a warmed hot working set, m * slab_bytes on cold misses.


def slab_bytes(cap: int, d: int) -> int:
    """H2D bytes one bucket-slab upload moves (int8 rows + scale + vsq
    + docids at the cache's fixed row capacity `cap`)."""
    return int(cap) * (int(d) + 12)


def tier_h2d_bytes(misses: int, cap: int, d: int) -> int:
    """Modeled PCIe bytes for a resolve with `misses` slab misses —
    zero on a full hit, one slab_bytes per missed bucket otherwise."""
    return int(misses) * slab_bytes(cap, d)


# -- 3. bytes-materialized model --------------------------------------------


def blockmax_selected_blocks(r: int, n_pad: int) -> int:
    """Blocks of BLOCK scores that the first level of ops/ivf.py
    _blocked_topk gathers, per query: r of them (the top-r of a row
    lies in the r blocks of largest maxima), never more than exist."""
    return min(min(r, n_pad), max(n_pad // BLOCK, 1))


def select_width(r: int, n_pad: int) -> int:
    """Scores a query that the widest sort of ops/ivf.py _select_topk
    takes at depth r over n_pad columns: the row itself where plain
    `lax.top_k` serves; else the N / BLOCK block maxima or what follows
    the gather, whichever is wider: the r * BLOCK gathered scores, or,
    where the second level engages, the r * BLOCK / GROUP group maxima
    (GROUP * r scores after them). tests/test_chip_compile.py
    holds the compiled programs to it, and every `kernel.{tag}` span of
    a full-scan site carries it."""
    r = min(r, n_pad)
    nblk = n_pad // BLOCK
    if n_pad % BLOCK or nblk < 4 * max(r, 128):
        return n_pad
    if r >= GROUP_MIN_R and r % GROUP == 0:
        return max(nblk, r * BLOCK // GROUP, r * GROUP)
    return max(nblk, r * BLOCK)


def scan_peak_bytes(b: int, n_pad: int) -> int:
    """Peak intermediate HBM bytes the full scan materializes per
    search: the [B, N] f32 score matrix, once (the selection reads it
    in the tiles the score fusion writes and copies nothing). PEAK
    resident, not total traffic. The chip's compiler reports 1.12x this
    as temp for the B=1024 program at N~1M (4.6 GB: the matrix plus the
    selection's small buffers; tests/test_chip_compile.py prints it and
    fails on a second score-sized buffer)."""
    return b * n_pad * F32


def scan_traffic_bytes(n_pad: int, d: int) -> int:
    """HBM bytes READ by the stage-1 pass over the database: the int8
    mirror rows, exactly once."""
    return n_pad * d  # int8: one byte per dim


# -- 4. HBM footprint model --------------------------------------------------


def mirror_footprint_bytes(n_cap: int, d: int, storage: str = "int8") -> int:
    """Resident device bytes of the docid-ordered compressed mirror:
    rows + per-row scale + per-row ||v||^2 (index/int8_mirror.py)."""
    width = d if storage == "int8" else (d + 1) // 2
    return n_cap * width + 2 * n_cap * F32


def binary_plane_bytes(n_cap: int, d: int) -> int:
    """Row PAYLOAD of the packed bit-plane mirror: ceil(d/8) bytes per
    row at the 512-aligned capacity. This — not the total — is the
    8x-density gate against the int8 mirror: the per-row aux columns
    (scale + offset, 8 bytes) ride identically on BOTH tiers, so the
    honest density claim compares payloads:
    8 * binary_plane_bytes <= mirror_footprint_bytes holds for every d
    (the int8 total is d + 8 bytes/row vs the plane's d/8), while the
    TOTAL ratio (d/8 + 8) / (d + 8) only approaches 1/8 as d grows —
    tests/test_perf_gates.py gates the payload form and PERF.md Tier 8
    states both numbers."""
    return int(n_cap) * (-(-int(d) // 8))


def binary_footprint_bytes(n_cap: int, d: int) -> int:
    """Resident device bytes of the flushed bit-plane mirror: packed
    sign planes + per-row scale + per-row ||approx||^2 — what
    Int8Mirror(storage="bits").device_bytes() reports and the device
    sampler must agree with."""
    return binary_plane_bytes(n_cap, d) + 2 * int(n_cap) * F32


def binary_scan_traffic_bytes(n_pad: int, d: int) -> int:
    """HBM bytes the stage-0 pass READS per query batch: each packed
    plane exactly once — 1/8 of the int8 scan's traffic term, the
    bandwidth headroom that makes stage 0 worth a third stage."""
    return int(n_pad) * (-(-int(d) // 8))


def refine_depths(k: int, n: int) -> tuple[int, int]:
    """Auto defaults for the three-stage candidate depths (r0, r1).

    Stage 0's sign estimator is selection-grade only, so its survivor
    set must be generous: r0 = 32x the int8 default's 10x-k rule,
    floored at 512 — still ~1e-3 of a 1M-row partition. Stage 1 then
    funnels to the proven int8 rerank depth r1 = max(10k, 128). Both
    clamp to the row count; both are runtime-tunable per request / via
    /ps/engine/config ("r0"/"r1" index params) with these as the
    documented fallback."""
    n = max(int(n), 1)
    r1 = min(max(10 * int(k), 128), n)
    r0 = min(max(32 * r1 // 10, 512), n)
    return max(r0, r1), r1


def raw_store_footprint_bytes(
    capacity: int, d: int, itemsize: int
) -> int:
    """Raw device buffer + sqnorm column (engine/raw_vector.py)."""
    return capacity * d * itemsize + capacity * F32


def per_device_bytes(
    sharded_bytes: int, replicated_bytes: int, n_shards: int
) -> int:
    """Resident HBM on EACH chip of a mesh placement: row-sharded state
    divides across the "data" axis (ceil: padded slabs), replicated
    state (coarse centroids, bucket tensors) rides whole on every chip.
    With n_shards == 1 this degenerates to the single-device footprint."""
    return replicated_bytes + -(-sharded_bytes // max(n_shards, 1))


def ivf_bucket_footprint_bytes(nlist: int, cap: int, d: int) -> int:
    """Probe-mode IVFPQ device state: [nlist, cap, d] int8 residuals +
    per-cluster scale + [nlist, cap] vsq + ids (index/ivf.py
    _publish_locked)."""
    return nlist * cap * d + nlist * F32 + 2 * nlist * cap * F32
