"""shard_map'd multi-chip kernels: sharded search + sharded k-means.

The SPMD layer of the engine. All cross-chip traffic is XLA collectives
over ICI (all_gather / psum) — no host round-trips inside a step
(SURVEY.md §2.4: the TPU-native communication backend; the reference's
NCCL-free design maps to pure data-parallel shard scan + on-device merge).

Layouts (built by parallel/mesh.py):
    base    [N_pad, d]  rows sharded over "data"
    queries [B_pad, d]  sharded over "query", replicated over "data"
    outputs [B_pad, k]  sharded over "query" (global docids)
    raw rerank store (engine/raw_vector.py device_buffer_sharded)
            [cap / pack, pack * d]  rows sharded over "data", with
            pack = mesh.row_pack(d): 96 -> 4, 64 -> 2, 128 and 768 -> 1.
            The chip lays a minor dimension under 128 lanes out
            column-major; a row gather then copies the whole shard, in
            every dispatch. Whole 128-lane super-rows are row-major as
            placed; `ops/ivf.py` `gather_rows` gathers `id // pack`, keeps
            `id % pack`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from vearch_tpu.engine.types import MetricType
from vearch_tpu.ops import kmeans as km
from vearch_tpu.ops.distance import brute_force_search, dot_precision, sqnorms
from vearch_tpu.ops.ivf import gather_rows as _gather_rows
from vearch_tpu.ops.perf_model import register_jit
from vearch_tpu.parallel import mesh as mesh_lib

NEG_INF = float("-inf")


def _mesh_tag(mesh: Mesh) -> str:
    return f"{mesh.shape['data']}x{mesh.shape['query']}"


@functools.lru_cache(maxsize=128)
def _flat_search_fn(mesh: Mesh, k: int, metric: MetricType):
    """Build-once jitted shard_map program. Re-creating the closure per
    call would retrace every search: jit's cache keys on function
    identity, so the callable itself is cached per (mesh, statics)."""

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("data", None), P("data"), P("data"), P("query", None)),
        out_specs=(P("query", None), P("query", None)),
        check_vma=False,
    )
    def run(b, sqn, v, q):
        local_k = min(k, b.shape[0])
        scores, ids = brute_force_search(q, b, v, local_k, metric, sqn)
        shard = jax.lax.axis_index("data")
        gids = jnp.where(ids >= 0, ids + shard * b.shape[0], -1)
        all_s = jax.lax.all_gather(scores, "data", axis=1, tiled=True)
        all_i = jax.lax.all_gather(gids, "data", axis=1, tiled=True)
        kk = min(k, all_s.shape[1])
        top_s, pos = jax.lax.top_k(all_s, kk)
        return top_s, jnp.take_along_axis(all_i, pos, axis=1)

    return register_jit(
        f"sharded.flat[{_mesh_tag(mesh)},k{k},{metric.name}]", run
    )


def sharded_flat_search(
    mesh: Mesh,
    base: jax.Array,      # [N_pad, d] sharded P("data", None)
    base_sqnorm: jax.Array,  # [N_pad] sharded P("data")
    valid: jax.Array,     # [N_pad] bool sharded P("data")
    queries: jax.Array,   # [B_pad, d] sharded P("query", None)
    k: int,
    metric: MetricType = MetricType.L2,
) -> tuple[jax.Array, jax.Array]:
    """Exact search over a row-sharded base: local top-k per shard, then
    all_gather over "data" + global re-top-k, all on device."""
    return _flat_search_fn(mesh, k, metric)(
        base, base_sqnorm, valid, queries
    )


def sharded_int8_search(
    mesh: Mesh,
    approx8: jax.Array,    # [N_pad, d] int8 sharded P("data", None)
    row_scale: jax.Array,  # [N_pad] sharded P("data")
    row_vsq: jax.Array,    # [N_pad] sharded P("data")
    valid: jax.Array,      # [N_pad] bool sharded P("data")
    queries: jax.Array,    # [B_pad, d] f32 sharded P("query", None)
    r: int,
    metric: MetricType = MetricType.L2,
    storage: str = "int8",
) -> tuple[jax.Array, jax.Array]:
    """Sharded compressed scan (the IVFPQ full-scan path across chips).
    `storage` follows the mirror tier: int8 rows or nibble-packed int4."""
    return _int8_search_fn(mesh, r, metric, storage)(
        approx8, row_scale, row_vsq, valid, queries
    )


@functools.lru_cache(maxsize=128)
def _int8_search_fn(mesh: Mesh, r: int, metric: MetricType,
                    storage: str = "int8"):
    from vearch_tpu.ops.ivf import int4_scan_candidates, int8_scan_candidates

    scan = int8_scan_candidates if storage == "int8" else int4_scan_candidates

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P("data", None), P("data"), P("data"), P("data"),
            P("query", None),
        ),
        out_specs=(P("query", None), P("query", None)),
        check_vma=False,
    )
    def run(a8, sc, vsq, v, q):
        local_r = min(r, a8.shape[0])
        scores, ids = scan(q, a8, sc, vsq, v, local_r, metric)
        shard = jax.lax.axis_index("data")
        # masked candidates come back as id=-1; keep them -1 globally
        # (a bare shard offset would turn them into real foreign docids)
        gids = jnp.where(ids >= 0, ids + shard * a8.shape[0], -1)
        all_s = jax.lax.all_gather(scores, "data", axis=1, tiled=True)
        all_i = jax.lax.all_gather(gids, "data", axis=1, tiled=True)
        rr = min(r, all_s.shape[1])
        top_s, pos = jax.lax.top_k(all_s, rr)
        return top_s, jnp.take_along_axis(all_i, pos, axis=1)

    return register_jit(
        f"sharded.int8[{_mesh_tag(mesh)},r{r},{metric.name},{storage}]",
        run,
    )


def _rerank_tail(b, bsqn, q, cand_i, shard, k: int,
                 rerank_metric: MetricType):
    """The exact tail of the two mesh rerank programs, inside their
    shard_map on shard `shard` of "data": score the merged candidates
    `cand_i` [B, r] (global row ids, -1 = none) against this shard's raw
    slab `b` as placed (`_gather_rows`) with `bsqn` [rows]; candidates
    this shard does not own score -inf and the pmax merge recovers the
    owner's exact score everywhere (ownership by the BASE slab's logical
    rows — the mirror and the raw buffer are padded to different
    alignments); then the final top-k."""
    with jax.named_scope("rerank"):
        local_nb = bsqn.shape[0]
        local = cand_i - shard * local_nb
        mine = (cand_i >= 0) & (local >= 0) & (local < local_nb)
        safe = jnp.clip(local, 0, local_nb - 1)
        vecs = _gather_rows(b, safe, q.shape[1])  # [B, rr, d]
        bvsq = bsqn[safe]
        qf = q.astype(b.dtype)
        rdots = jax.lax.dot_general(
            qf, vecs, (((1,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=dot_precision(qf, vecs),
        )
        if rerank_metric is MetricType.L2:
            rscores = -(sqnorms(qf)[:, None] - 2.0 * rdots + bvsq)
        elif rerank_metric is MetricType.COSINE:
            qn = jnp.sqrt(jnp.maximum(sqnorms(qf), 1e-30))[:, None]
            vn = jnp.sqrt(jnp.maximum(bvsq, 1e-30))
            rscores = rdots / (qn * vn)
        else:
            rscores = rdots
        rscores = jnp.where(mine, rscores, NEG_INF)
    with jax.named_scope("pmax"):
        rscores = jax.lax.pmax(rscores, "data")
    with jax.named_scope("rerank"):
        kk = min(k, rscores.shape[1])
        out_s, out_pos = jax.lax.top_k(rscores, kk)
        out_i = jnp.take_along_axis(cand_i, out_pos, axis=1)
        return out_s, jnp.where(jnp.isfinite(out_s), out_i, -1)


def sharded_ivf_search(
    mesh: Mesh,
    centroids: jax.Array | None,  # [nlist, d] f32 replicated (None: no probe)
    assign: jax.Array | None,     # [N_pad] i32 row->cluster, sharded P("data")
    approx8: jax.Array,           # [N_pad, d] int8 / [N_pad, d/2] packed int4
    row_scale: jax.Array,         # [N_pad] f32 sharded P("data")
    row_vsq: jax.Array,           # [N_pad] f32 sharded P("data")
    valid: jax.Array,             # [N_pad] bool sharded P("data")
    base: jax.Array,              # [cap/pack, pack*d] raw rows, P("data", None)
    base_sqnorm: jax.Array,       # [cap] f32 sharded P("data")
    queries: jax.Array,           # [B_pad, d] f32 sharded P("query", None)
    r: int,
    k: int,
    scan_metric: MetricType = MetricType.L2,
    rerank_metric: MetricType = MetricType.L2,
    storage: str = "int8",
    nprobe: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """The pod-slice IVF serving program: coarse probe -> per-shard
    compressed scan -> all_gather top-r merge -> exact rerank against
    the sharded raw base -> pmax merge + final top-k, as ONE jitted
    shard_map program. Nothing touches the host between the query
    replicate and the final [B, k] device_get.

    nprobe=0 disables the coarse gate (docid-ordered full scan — the
    IVFPQ "full" mode); nprobe>0 masks every shard's rows to the probed
    cells using the REPLICATED coarse quantizer, so probe selection is
    computed redundantly per shard instead of paying a collective."""
    fn = _ivf_search_fn(
        mesh, r, k, scan_metric, rerank_metric, storage, nprobe
    )
    if nprobe > 0:
        return fn(centroids, assign, approx8, row_scale, row_vsq, valid,
                  base, base_sqnorm, queries)
    return fn(approx8, row_scale, row_vsq, valid, base, base_sqnorm, queries)


@functools.lru_cache(maxsize=128)
def _ivf_search_fn(
    mesh: Mesh, r: int, k: int, scan_metric: MetricType,
    rerank_metric: MetricType, storage: str, nprobe: int,
):
    from vearch_tpu.ops.ivf import _coarse_probes, _select_topk, unpack_int4

    probed = nprobe > 0
    # queries ride the "query" axis (last in_spec / both out_specs) —
    # every stage of the program is per-query-row except the "data"
    # collectives, so a query_axis>1 mesh splits the batch across its
    # query shards for free; centroids stay replicated (every query
    # shard recomputes its own probes, same as every data shard does)
    mirror_specs = (P("data", None), P("data"), P("data"), P("data"))
    rerank_specs = (P("data", None), P("data"), P("query", None))
    if probed:
        in_specs = (P(None, None), P("data")) + mirror_specs + rerank_specs
    else:
        in_specs = mirror_specs + rerank_specs

    def program(*args):
        if probed:
            cents, assign, a8, sc, vsq, v, b, bsqn, q = args
        else:
            a8, sc, vsq, v, b, bsqn, q = args
        local_n = sc.shape[0]
        ok = v[None, :]
        if probed:
            # every shard holds the full coarse quantizer, so probe
            # selection is recomputed identically per shard — cheaper
            # than a collective for any realistic nlist. The per-row
            # gate is a [B, nlist] cell mask gathered by the shard's own
            # row->cluster assignment.
            probes = _coarse_probes(q, cents, min(nprobe, cents.shape[0]))
            cell = jnp.zeros(
                (q.shape[0], cents.shape[0]), dtype=bool
            ).at[jnp.arange(q.shape[0])[:, None], probes].set(True)
            ok = ok & cell[:, jnp.maximum(assign, 0)]
        # the stages carry the named scopes of ops/ivf.py
        # int8_scan_rerank (block_max and select come with
        # _select_topk), plus the two only a mesh has: merge and pmax
        with jax.named_scope("score"):
            rows = a8.astype(jnp.bfloat16) if storage == "int8" \
                else unpack_int4(a8)
            dots = jax.lax.dot_general(
                q.astype(jnp.bfloat16), rows, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * sc[None, :]
            if scan_metric is MetricType.L2:
                scores = -(sqnorms(q)[:, None] - 2.0 * dots + vsq[None, :])
            else:
                scores = dots
            scores = jnp.where(ok, scores, NEG_INF)
        top_s, top_i = _select_topk(scores, min(r, local_n))
        shard = jax.lax.axis_index("data")
        with jax.named_scope("merge"):
            gids = jnp.where(top_i >= 0, top_i + shard * local_n, -1)
            all_s = jax.lax.all_gather(top_s, "data", axis=1, tiled=True)
            all_i = jax.lax.all_gather(gids, "data", axis=1, tiled=True)
            rr = min(r, all_s.shape[1])
            cand_s, pos = jax.lax.top_k(all_s, rr)
            cand_i = jnp.take_along_axis(all_i, pos, axis=1)
        return _rerank_tail(b, bsqn, q, cand_i, shard, k, rerank_metric)

    # jit names the XLA module after the function: the serving program
    # is `jit_sharded_fused_scan_rerank` on the device trace (probe
    # regime `jit_sharded_probe_scan_rerank`), its dispatch tag, where
    # every other shard_map program of this file is `jit_run`
    program.__name__ = program.__qualname__ = (
        "sharded_probe_scan_rerank" if probed
        else "sharded_fused_scan_rerank")
    run = jax.jit(shard_map(
        program, mesh=mesh, in_specs=in_specs,
        out_specs=(P("query", None), P("query", None)), check_vma=False,
    ))
    return register_jit(
        f"sharded.ivf_fused[{_mesh_tag(mesh)},r{r},k{k},"
        f"{scan_metric.name},{rerank_metric.name},{storage},p{nprobe}]",
        run,
    )


def sharded_binary_refine(
    mesh: Mesh,
    planes: jax.Array,       # [N_pad, d/8] uint8 sharded P("data", None)
    p_scale: jax.Array,      # [N_pad] f32 sharded P("data")
    p_vsq: jax.Array,        # [N_pad] f32 sharded P("data")
    approx8: jax.Array,      # [N_pad, d] int8 / [N_pad, d/2] int4-packed
    m_scale: jax.Array,      # [N_pad] f32 sharded P("data")
    m_vsq: jax.Array,        # [N_pad] f32 sharded P("data")
    valid: jax.Array,        # [N_pad] bool sharded P("data")
    base: jax.Array,         # [cap/pack, pack*d] raw rows, P("data", None)
    base_sqnorm: jax.Array,  # [cap] f32 sharded P("data")
    queries: jax.Array,      # [B_pad, d] f32 sharded P("query", None)
    r0: int,
    r1: int,
    k: int,
    scan_metric: MetricType = MetricType.L2,
    rerank_metric: MetricType = MetricType.L2,
    storage: str = "int8",
) -> tuple[jax.Array, jax.Array]:
    """The pod-slice three-stage refinement program: bit planes, int8
    mirror, and raw base all row-sharded in lockstep over "data"
    (identical ShardedRowCache alignment, so local row offsets agree);
    stages 0-1 run entirely shard-local — a shard's stage-0 survivors
    are by construction rows it owns, so the int8 rescore needs no
    collective — then ONE all_gather merges the per-shard top-r1 sets
    and the exact rerank + pmax merge finishes exactly like
    sharded_ivf_search. ONE jitted shard_map program end to end."""
    return _binary_refine_fn(
        mesh, r0, r1, k, scan_metric, rerank_metric, storage
    )(planes, p_scale, p_vsq, approx8, m_scale, m_vsq, valid,
      base, base_sqnorm, queries)


@functools.lru_cache(maxsize=128)
def _binary_refine_fn(
    mesh: Mesh, r0: int, r1: int, k: int, scan_metric: MetricType,
    rerank_metric: MetricType, storage: str,
):
    from vearch_tpu.ops.binary_scan import _binary_scores, _mirror_rescore
    from vearch_tpu.ops.ivf import _select_topk

    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P("data", None), P("data"), P("data"),
            P("data", None), P("data"), P("data"), P("data"),
            P("data", None), P("data"), P("query", None),
        ),
        out_specs=(P("query", None), P("query", None)),
        check_vma=False,
    )
    def run(pl, psc, pvsq, a8, msc, mvsq, v, b, bsqn, q):
        local_n = psc.shape[0]
        # stage 0: local binary scan over this shard's bit planes
        scores = _binary_scores(q, pl, psc, pvsq, v, scan_metric)
        _, c0 = _select_topk(scores, min(r0, local_n))
        # stage 1: rescore this shard's own survivors against its
        # int8/int4 mirror slab — ids are still shard-local
        top_s, top_i = _mirror_rescore(
            q, c0, a8, msc, mvsq, min(r1, local_n), scan_metric, storage
        )
        shard = jax.lax.axis_index("data")
        gids = jnp.where(top_i >= 0, top_i + shard * local_n, -1)
        all_s = jax.lax.all_gather(top_s, "data", axis=1, tiled=True)
        all_i = jax.lax.all_gather(gids, "data", axis=1, tiled=True)
        rr = min(r1, all_s.shape[1])
        cand_s, pos = jax.lax.top_k(all_s, rr)
        cand_i = jnp.take_along_axis(all_i, pos, axis=1)
        # stage 2: exact rerank against the raw slab, pmax ownership merge
        return _rerank_tail(b, bsqn, q, cand_i, shard, k, rerank_metric)

    return register_jit(
        f"sharded.binary_refine[{_mesh_tag(mesh)},r0_{r0},r1_{r1},k{k},"
        f"{scan_metric.name},{rerank_metric.name},{storage}]",
        run,
    )


def sharded_kmeans_step(
    mesh: Mesh,
    x: jax.Array,        # [N_pad, d] sharded P("data", None)
    valid: jax.Array,    # [N_pad] bool sharded P("data")
    centroids: jax.Array,  # [k, d] replicated
    reseed: jax.Array,   # [k, d] replicated
    chunk: int = 16384,
) -> jax.Array:
    """One Lloyd round over sharded data: per-shard partial stats, psum
    over "data", identical centroid update everywhere (the distributed
    training step of the coarse quantizer / PQ codebooks)."""
    return _kmeans_step_fn(mesh, chunk)(x, valid, centroids, reseed)


@functools.lru_cache(maxsize=32)
def _kmeans_step_fn(mesh: Mesh, chunk: int):
    @jax.jit
    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P("data", None), P("data"), P(None, None), P(None, None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    def step(xs, vs, c, rs):
        local_chunk = min(chunk, max(256, xs.shape[0]))
        rem = (-xs.shape[0]) % local_chunk
        if rem:
            xs = jnp.pad(xs, ((0, rem), (0, 0)))
            vs = jnp.pad(vs, (0, rem))
        sums, counts = km.kmeans_partials(xs, vs, c, chunk=local_chunk)
        # inputs are replicated over "query", so reducing over "data" alone
        # leaves every device with identical full stats
        sums = jax.lax.psum(sums, "data")
        counts = jax.lax.psum(counts, "data")
        return km.centroids_from_partials(sums, counts, rs)

    return step


def train_kmeans_sharded(
    mesh: Mesh, x_host: np.ndarray, k: int, iters: int = 10, seed: int = 0
) -> jax.Array:
    """Full multi-chip k-means: k-means++ init on a host sample, then
    `iters` sharded Lloyd rounds."""
    n = x_host.shape[0]
    x_host = np.asarray(x_host, dtype=np.float32)
    sample = x_host[
        np.random.default_rng(seed).choice(n, min(n, 65_536), replace=False)
    ]
    init = km.kmeanspp_init(jax.random.PRNGKey(seed), jnp.asarray(sample), k)
    reseed_rows = x_host[
        np.random.default_rng(seed + 1).choice(n, k, replace=n < k)
    ]

    x_dev, n_orig = mesh_lib.shard_rows(mesh, x_host)
    valid_host = np.arange(x_dev.shape[0]) < n_orig
    valid_dev, _ = mesh_lib.shard_rows(mesh, valid_host)
    cents = mesh_lib.replicate(mesh, init)
    reseed = mesh_lib.replicate(mesh, reseed_rows)
    for _ in range(iters):
        cents = sharded_kmeans_step(mesh, x_dev, valid_dev, cents, reseed)
    return cents


class ShardedFlatSearcher:
    """Holds a row-sharded database on a mesh and serves exact search —
    the multi-chip deployment of a FLAT partition (one partition spanning
    a TPU slice; the cluster layer still shards *across* partitions)."""

    def __init__(
        self,
        mesh: Mesh,
        base: np.ndarray,
        metric: MetricType = MetricType.L2,
        store_dtype: str = "bfloat16",
    ):
        from vearch_tpu.ops.distance import sqnorms

        self.mesh = mesh
        self.metric = metric
        self.n = base.shape[0]
        self.store_dtype = jnp.dtype(store_dtype)
        base = np.asarray(base, dtype=np.float32)
        self.base, _ = mesh_lib.shard_rows(mesh, base.astype(self.store_dtype))
        self.sqnorm = sqnorms(self.base)
        valid = np.arange(self.base.shape[0]) < self.n
        self.valid, _ = mesh_lib.shard_rows(mesh, valid)

    def search(self, queries: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
        q, b = mesh_lib.shard_queries(
            self.mesh, np.asarray(queries, np.float32).astype(self.store_dtype)
        )
        scores, ids = sharded_flat_search(
            self.mesh, self.base, self.sqnorm, self.valid, q, k, self.metric
        )
        scores, ids = jax.device_get((scores, ids))
        return scores[:b], ids[:b]
