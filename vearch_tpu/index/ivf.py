"""IVFFLAT and IVFPQ index types.

TPU-native re-design of the reference's realtime IVF indexes (reference:
index/impl/gamma_index_ivfflat.cc:198, gamma_index_ivfpq.cc:36 + the
RTInvertIndex realtime lists, index/realtime/realtime_invert_index.h:24).

Where the reference grows per-bucket linked segments that CPU threads scan,
TPU wants static-shaped dense arrays:

- host side keeps per-cluster docid lists (cheap python/numpy appends —
  the realtime ingest structure);
- `_publish` packs them into padded [nlist, cap, ...] device arrays
  (cap = max bucket length rounded up); a publish happens lazily on the
  first search after new rows were absorbed — the generation-swap pattern
  (build arrays, then swap references atomically; IVFFLAT, whose table
  holds the raw rows, lets the old generation go before it places the
  new one and hands a search one generation's arrays under the lock);
- deletes never touch the index: the engine's validity mask is applied
  in-kernel per slot (IVFFLAT hands its program the mask in the table's
  own slot-major order, built once per (published table, mask) by one
  scatter over the rows: `IVFFlatIndex._bucket_ok`).

Search: ops/ivf.py scan kernels + exact rerank against the raw device
buffer. Rerank depth `rerank` (default 4*k, min 64… capped by candidates)
is the recall knob on top of nprobe.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from vearch_tpu.engine.raw_vector import RawVectorStore
from vearch_tpu.engine.types import IndexParams, MetricType
from vearch_tpu.index.base import VectorIndex
from vearch_tpu.index.int8_mirror import Int8Mirror
from vearch_tpu.index.registry import register_index
from vearch_tpu.ops import ivf as ivf_ops
from vearch_tpu.ops import kmeans as km
from vearch_tpu.ops import perf_model
from vearch_tpu.ops import pq as pq_ops
from vearch_tpu.ops.distance import to_device_mask
from vearch_tpu.tools import lockcheck


#: rows of one piece of a bulk absorb: the default training sample's
#: size, so that the build assigns its rows with the program that
#: assigned the sample
BULK_ROWS = 262_144


def _bulk_rows(fn, rows: np.ndarray) -> np.ndarray:
    """A row-wise device function over host rows. More than BULK_ROWS
    (an index build's absorb) go up in pieces of exactly BULK_ROWS, the
    last one zero-padded: one compiled shape however many rows the
    partition has. Compiled for its own row count, a 4M x 96 build
    spent 107 s of its 139 s of `assign` in the compiler (PERF.md
    section 6, PR 27). Every row's result is its own, so the pieces
    give what one call gives."""
    n = rows.shape[0]
    if n <= BULK_ROWS:
        return np.asarray(fn(jnp.asarray(rows)))
    out = []
    for lo in range(0, n, BULK_ROWS):
        piece = rows[lo:lo + BULK_ROWS]
        real = piece.shape[0]
        if real < BULK_ROWS:
            piece = np.concatenate([piece, np.zeros(
                (BULK_ROWS - real,) + piece.shape[1:], piece.dtype)])
        out.append(np.asarray(fn(jnp.asarray(piece)))[:real])
    return np.concatenate(out)


class _IVFBase(VectorIndex):
    needs_training = True

    def __init__(self, params: IndexParams, store: RawVectorStore):
        super().__init__(params, store)
        self.nlist = int(params.get("ncentroids", params.get("nlist", 256)))
        self.default_nprobe = int(params.get("nprobe", 16))
        self.train_sample = int(params.get("training_sample", 262_144))
        self.train_iters = int(params.get("train_iters", 10))
        # coarse quantizer choice (reference: gamma_index_ivfpq.h:1258
        # quantizer_type_ — FLAT vs HNSW over the centroids). On TPU the
        # [B, nlist] matmul is usually the right answer; the HNSW graph
        # wins when probe selection should stay on HOST — tiny batches
        # or huge nlist, where a device dispatch per coarse step costs
        # more than an O(log nlist) graph walk.
        self.quantizer_type = str(
            params.get("quantizer_type", "flat")
        ).lower()
        self._coarse_graph = None
        self.centroids: jax.Array | None = None  # [nlist, d] f32
        self._members: list[list[int]] = []  # per-cluster docid lists (host)
        self._dirty = True
        # published device state
        self._bucket_ids: jax.Array | None = None
        self._cap = 0
        #: the last publish, as `ivf_info` reports it (None before one)
        self._published: dict[str, Any] | None = None
        #: slot-major validity masks built / found again (IVFFLAT's
        #: `_bucket_ok`; an index that looks its mask up by docid keeps 0)
        self._mask_builds = 0
        self._mask_hits = 0

    def _device_state_arrays(self) -> tuple:
        """Device tensors this index keeps resident beyond the raw store
        (footprint model input; subclasses extend)."""
        return (self.centroids, self._bucket_ids)

    def device_footprint_bytes(self) -> int:
        total = super().device_footprint_bytes()
        for a in self._device_state_arrays():
            if a is not None:
                total += int(a.size) * a.dtype.itemsize
        return total

    # -- training ------------------------------------------------------------

    def _sample(self, x: np.ndarray) -> np.ndarray:
        if x.shape[0] <= self.train_sample:
            return x
        idx = np.random.default_rng(0).choice(
            x.shape[0], self.train_sample, replace=False
        )
        return x[idx]

    def _maybe_normalize(self, x: np.ndarray) -> np.ndarray:
        """Cosine rides the IP machinery on normalized vectors."""
        if self.metric is MetricType.COSINE:
            n = np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-15)
            return (x / n).astype(np.float32)
        return x

    #: "DxQ" mesh tag of the last coarse-quantizer training, None for
    #: the single-device trainer (build jobs and build.train spans
    #: record it)
    last_train_mesh: str | None = None

    def _train_mesh(self):
        """Mesh for coarse-quantizer training, or None for the single-
        device path. Opt-in (``mesh_train: true``): the sharded
        trainer's k-means++ init subsamples differently from the
        single-device trainer, so flipping it on changes the trained
        centroids — an explicit build-time decision, not an ambient one
        that would silently shift recall when the device count changes.
        """
        if not bool(self.params.get("mesh_train", False)):
            return None
        if len(jax.devices()) <= 1:
            return None
        return self._serving_mesh(None)

    def _serving_mesh(self, params: dict | None):
        """The mesh this index places/serves on: the ``mesh_shape`` knob
        (engine apply_config fans it into index params; per-request
        override wins), defaulting to the all-devices data×1 mesh."""
        from vearch_tpu.parallel import mesh as mesh_lib

        shape = (params or {}).get(
            "mesh_shape", self.params.get("mesh_shape")
        )
        return mesh_lib.mesh_from_shape(shape)

    def train(self, sample: np.ndarray) -> None:
        x = self._maybe_normalize(self._sample(np.asarray(sample, np.float32)))
        mesh = self._train_mesh()
        if mesh is not None:
            # multi-chip coarse training: per-shard partial sums, psum
            # over "data" (parallel/sharded.py train_kmeans_sharded) —
            # index builds use all chips instead of serializing Lloyd
            # rounds on one
            from vearch_tpu.parallel.sharded import train_kmeans_sharded

            self.centroids = train_kmeans_sharded(
                mesh, x, k=self.nlist, iters=self.train_iters
            )
            self.last_train_mesh = (
                f"{mesh.shape['data']}x{mesh.shape['query']}"
            )
        else:
            self.centroids = km.train_kmeans(
                jnp.asarray(x), k=self.nlist, iters=self.train_iters
            )
            self.last_train_mesh = None
        self._members = [[] for _ in range(self.nlist)]
        self._build_coarse_graph()
        self._train_extra(x)
        self.trained = True

    def _build_coarse_graph(self) -> None:
        if self.quantizer_type != "hnsw":
            return
        try:
            from vearch_tpu.native.hnsw_graph import HnswGraph

            g = HnswGraph(self.store.dimension, m=16, ef_construction=200,
                          ip=False)
            g.add(np.asarray(self.centroids, dtype=np.float32))
            self._coarse_graph = g
        except RuntimeError as e:
            from vearch_tpu.utils import log

            log.warn("hnsw coarse quantizer unavailable (%s); "
                     "falling back to flat", e)
            self.quantizer_type = "flat"
            self._coarse_graph = None

    def _assign(self, rows: np.ndarray) -> np.ndarray:
        """Cluster assignment for absorb: device matmul (exact) or the
        host HNSW graph walk (quantizer_type=hnsw — no device dispatch,
        which matters when absorb runs on the cluster's write path)."""
        if self._coarse_graph is not None:
            _s, ids = self._coarse_graph.search(rows, 1, ef=96)
            return ids[:, 0].astype(np.int64)
        return _bulk_rows(
            lambda x: km.assign_clusters(x, self.centroids), rows)

    def _host_probes(self, q: np.ndarray, nprobe: int) -> np.ndarray | None:
        """[B, nprobe] probe cells from the host graph, or None for the
        in-kernel matmul selection."""
        if self._coarse_graph is None:
            return None
        _s, ids = self._coarse_graph.search(
            q, min(nprobe, self.nlist), ef=max(2 * nprobe, 64)
        )
        # -1 padding (graph came up short) passes through: the scan
        # kernels mask those probe steps entirely — clamping to a real
        # cell here would scan it twice and DUPLICATE its docids
        return np.ascontiguousarray(ids, dtype=np.int32)

    def _train_extra(self, sample: np.ndarray) -> None:
        pass

    # -- realtime absorb (reference: AddRTVecsToIndex) ------------------------

    def absorb(self, upto: int) -> None:
        with self._absorb_lock:
            # recheck under the lock: a concurrent search/build thread may
            # have absorbed the same range already
            if not self.trained or upto <= self.indexed_count:
                self.indexed_count = max(self.indexed_count, upto)
                return
            start = self.indexed_count
            rows = self._maybe_normalize(
                self.store.host_view()[start:upto].astype(np.float32)
            )
            assign = self._assign(rows)
            self._absorb_rows(rows, assign, start)
            # vectorised bucket grouping: argsort by cluster + split beats a
            # python append loop ~50x at 1M rows
            order = np.argsort(assign, kind="stable")
            sorted_assign = assign[order]
            docids = order.astype(np.int64) + start
            boundaries = np.searchsorted(
                sorted_assign, np.arange(self.nlist + 1)
            )
            for c in np.unique(sorted_assign):
                lo, hi = boundaries[c], boundaries[c + 1]
                self._members[int(c)].extend(docids[lo:hi].tolist())
            self.indexed_count = upto
            self._dirty = True

    def _absorb_rows(
        self, rows: np.ndarray, assign: np.ndarray, start_docid: int
    ) -> None:
        pass

    # -- publish -------------------------------------------------------------

    def _bucket_shape(self) -> int:
        longest = max((len(mm) for mm in self._members), default=0)
        return max(128, -(-longest // 128) * 128)

    def _publish_ids(self) -> np.ndarray:
        cap = self._bucket_shape()
        ids = np.full((self.nlist, cap), -1, dtype=np.int32)
        for c, mm in enumerate(self._members):
            if mm:
                ids[c, : len(mm)] = mm
        self._cap = cap
        self._bucket_ids = jnp.asarray(ids)
        return ids

    def _note_publish(self, publish: "ivf_ops.phase",
                      *arrays: jax.Array) -> None:
        """A publish is done: the whole padded table went up again
        (Python loop over the lists + one upload). Keep what it placed
        for `ivf_info` and leave an `ivf.publish` span: on the search
        that paid for it when that one is profiled, else process-level
        (ops/ivf.py phase), beside `engine.replace_raw`."""
        rows = sum(len(mm) for mm in self._members)
        slots = self.nlist * self._cap
        table = {
            "rows": rows, "nlist": self.nlist, "cap": self._cap,
            "bytes": sum(int(a.nbytes) for a in arrays),
            "fill": round(rows / slots, 6) if slots else 0.0,
        }
        t1 = publish.close(table)
        self._published = {
            **table, "seconds": round(t1 - publish.t0, 3),
            "publishes": (self._published or {}).get("publishes", 0) + 1}

    def ivf_info(self) -> dict[str, Any] | None:
        """The published bucket table (rows held, lists, slots a list,
        device bytes, rows / slots), how long its publish took and how
        many there have been, and how many slot-major validity masks
        were built for it and found again; None until the first."""
        if not self._published:
            return None
        return {**self._published, "mask_builds": self._mask_builds,
                "mask_hits": self._mask_hits}

    def _valid_device(self, valid_mask, n: int) -> jax.Array:
        # pad to store capacity so the probe kernels keep a stable input
        # shape across ingest (capacity only changes on rare doublings)
        return to_device_mask(valid_mask, n, max(self.store.capacity, 1))

    def _rerank_depth(self, k: int, params: dict | None) -> int:
        """Exact-rerank candidate depth — the recall knob on top of the
        quantized scan (rerank cost is one [B, r, d] gather+matvec,
        negligible vs the scan itself, so the default is generous)."""
        p = params or {}
        r = int(p.get("rerank", self.params.get("rerank", max(10 * k, 128))))
        return max(r, k)

    def _exact_rerank_enabled(self, params: dict | None) -> bool:
        """Whether the exact raw-store rerank pass runs after the
        quantized scan. SCANN's reordering=false flips this off."""
        return True

    def _nprobe(self, params: dict | None) -> int:
        p = params or {}
        return min(int(p.get("nprobe", self.default_nprobe)), self.nlist)

    def _pad_to_k(
        self, scores: np.ndarray, ids: np.ndarray, k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        if scores.shape[1] >= k:
            return scores[:, :k], ids[:, :k]
        pad = k - scores.shape[1]
        return (
            np.pad(scores, ((0, 0), (0, pad)), constant_values=float("-inf")),
            np.pad(ids, ((0, 0), (0, pad)), constant_values=-1),
        )

    def cell_populations(self) -> list[int] | None:
        """Live per-cell member counts (quality drift gauge input)."""
        with self._absorb_lock:
            if not self.trained:
                return None
            return [len(mm) for mm in self._members]

    def dump_state(self) -> dict[str, Any]:
        if not self.trained:
            return {}
        return {
            "centroids": np.asarray(self.centroids),
            "indexed_count": np.int64(self.indexed_count),
        }

    def load_state(self, state: dict[str, Any]) -> None:
        if "centroids" in state:
            self.centroids = jnp.asarray(state["centroids"])
            self._build_coarse_graph()  # rebuilt, not persisted: cheap
            self.trained = True
            self._members = [[] for _ in range(self.nlist)]
            # re-absorb everything: assignments are recomputed, codes
            # re-encoded — raw vectors are the durable source of truth
            # (reference: index is rebuildable from raw store)
            self.indexed_count = 0
            if "codebooks" in state:
                self._load_codebooks(state)
            self.absorb(self.store.count)

    def _load_codebooks(self, state: dict[str, Any]) -> None:
        pass


@register_index("IVFFLAT")
class IVFFlatIndex(_IVFBase):
    """Realtime IVF over raw vectors (reference: gamma_index_ivfflat.cc)."""

    def __init__(self, params: IndexParams, store: RawVectorStore):
        super().__init__(params, store)
        self._bucket_vecs: jax.Array | None = None
        self._bucket_sqnorm: jax.Array | None = None
        #: [rows held] the flat slot `c * cap + j` of every row of the
        #: published table (a row it does not hold: the spare slot
        #: `nlist * cap`), kept on the host beside it
        self._slot_of: np.ndarray | None = None
        #: (source mask, n, [nlist, cap] device mask) of the published
        #: table: ONE entry, dropped with the table
        self._mask_entry: tuple[Any, int, jax.Array] | None = None

    def _device_state_arrays(self) -> tuple:
        return super()._device_state_arrays() + (
            self._bucket_vecs, self._bucket_sqnorm,
            self._mask_entry[2] if self._mask_entry else None,
        )

    def _bucket_shape(self) -> int:
        """A list longer than one scan step's tile gets whole tiles
        (ops/ivf.py `probe_tile`: the program then scans it tile by
        tile and gathers no slice wider than the compiler keeps in one
        piece)."""
        cap = super()._bucket_shape()
        tile = ivf_ops.probe_tile_rows(
            self.store.dimension * self.store.store_dtype.itemsize)
        return cap if cap <= tile else -(-cap // tile) * tile

    def _publish(self, valid_mask, n: int) -> tuple[tuple, dict[str, Any]]:
        """Publish if a publish is due, and return the table to scan,
        (vecs, sqnorm, ids, ok) of ONE generation, with what
        `_note_publish` kept of it and the `mask` / `mask_ms` tags of
        `_bucket_ok`. Under the absorb lock: a concurrent absorb would
        grow _members between capacity sizing and the fill loop (found
        by the concurrency stress test), and a search that read the
        arrays one by one beside a publish could pair one generation's
        ids, or its mask, with another's rows."""
        with self._absorb_lock:
            if self._dirty or self._bucket_vecs is None:
                self._publish_locked()
            t0 = time.monotonic()
            ok, how = self._bucket_ok(valid_mask, n)
            tags = {**self._published, "mask": how,
                    "mask_ms": round((time.monotonic() - t0) * 1e3, 3)}
            return (self._bucket_vecs, self._bucket_sqnorm,
                    self._bucket_ids, ok), tags

    def _bucket_ok(self, valid_mask, n: int) -> tuple[jax.Array, str]:
        """The validity mask in the published table's slot-major order,
        `[nlist, cap]` bool on the device: true where the slot holds a
        row below `n` that `valid_mask` (the engine's device-resident
        alive mask, a host filter mask, or None: all alive) lets
        through; padding, and a row absorbed after the mask was taken,
        stay false.

        Kept per (published table, source mask, n), one entry, by
        identity with a strong reference to the source (a live
        object's id cannot be reused; `_mesh_valid_sharded`'s rule):
        the engine hands back the SAME alive mask until a delete or a
        write, and the same filter mask on a filter-cache hit. A miss
        is one scatter over ROWS on the host (1M rows into 8.4M slots:
        about 10 ms) and one upload, where the program's per-slot
        lookup `valid[ids]` cost 135 ms of every dispatch on the chip
        (PERF.md section 6, PR 33). Call under the absorb lock."""
        entry = self._mask_entry
        if entry is not None and entry[0] is valid_mask and entry[1] == n:
            self._mask_hits += 1
            return entry[2], "hit"
        slot_of = self._slot_of
        slots = self.nlist * self._cap
        ok = np.zeros(slots + 1, dtype=np.bool_)
        m = min(n, slot_of.shape[0])
        if valid_mask is None:
            ok[slot_of[:m]] = True
        else:
            # a device-resident mask comes down once per build
            v = np.asarray(valid_mask)[:m]
            ok[slot_of[:v.shape[0]]] = v
        # the spare slot took the rows the table does not hold
        dev = jnp.asarray(ok[:slots].reshape(self.nlist, self._cap))
        # the old entry (and its 1 byte a slot on the device) goes as
        # the new one is kept
        self._mask_entry = (valid_mask, n, dev)
        self._mask_builds += 1
        return dev, "built"

    def _publish_locked(self) -> None:
        publish = ivf_ops.phase("ivf.publish")
        # the old table goes first, its mask with it: every search that
        # could scan it is waiting for this lock (it saw _dirty), and
        # old and new side by side are twice the table on the device
        # (2 x 4.4 GB of a chip's 16 at 1M x 128 under a cap of 8192)
        self._bucket_vecs = self._bucket_sqnorm = self._bucket_ids = None
        self._mask_entry = self._slot_of = None
        ids = self._publish_ids()
        cap = ids.shape[1]
        # where each row sits: one pass over the host ids just built
        flat = ids.reshape(-1)
        held = np.flatnonzero(flat >= 0)
        slot_of = np.full(self.indexed_count, flat.shape[0], dtype=np.int32)
        slot_of[flat[held]] = held
        self._slot_of = slot_of
        d = self.store.dimension
        host = self.store.host_view()
        vecs = np.zeros((self.nlist, cap, d), dtype=np.float32)
        for c, mm in enumerate(self._members):
            if mm:
                vecs[c, : len(mm)] = self._maybe_normalize(
                    host[np.asarray(mm, dtype=np.int64)]
                )
        self._bucket_vecs = jnp.asarray(vecs, dtype=self.store.store_dtype)
        self._bucket_sqnorm = ivf_ops.bucket_sqnorms(self._bucket_vecs)
        self._dirty = False
        self._note_publish(publish, self._bucket_vecs, self._bucket_sqnorm,
                           self._bucket_ids)

    def search(
        self,
        queries: np.ndarray,
        k: int,
        valid_mask: np.ndarray | None,
        params: dict | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        assert self.trained, "IVFFLAT search before training"
        probe = ivf_ops.phase("ivf.probe", request_only=True)
        (bucket_vecs, bucket_sqnorm, bucket_ids, bucket_ok), published = (
            self._publish(valid_mask, self.store.count))
        nprobe = self._nprobe(params)
        r = min(self._rerank_depth(k, params), published["cap"] * nprobe)
        q = self._maybe_normalize(np.asarray(queries, np.float32))
        metric = (
            MetricType.INNER_PRODUCT
            if self.metric is MetricType.COSINE
            else self.metric
        )
        host_probes = self._host_probes(q, nprobe)
        depth = min(max(r, k), 2048)
        cap = published["cap"]
        # the probe phase: from the index's entry to the launch (a
        # publish if one was due, the mask, host probe selection), with
        # what the program is about to scan: `nprobe` lists of `cap`
        # slots a query, `fill` of them holding a row, under a slot-
        # major mask that was found again (`hit`) or `built`, and the
        # widest sort of its selection
        probe.close(
            {"nprobe": nprobe, "cap": cap,
             "fill": published["fill"], "mask": published["mask"],
             "mask_ms": published["mask_ms"],
             "select_width": perf_model.probe_select_width(
                 depth, nprobe * cap, ivf_ops.probe_tile(
                     cap, self.store.dimension
                     * self.store.store_dtype.itemsize))})
        ivf_ops.note_dispatch("ivfflat_scan")
        scores, ids = ivf_ops.ivfflat_candidates(
            jnp.asarray(q, dtype=self.store.store_dtype),
            self.centroids,
            bucket_vecs,
            bucket_sqnorm,
            bucket_ids,
            bucket_ok,
            nprobe,
            depth,
            metric,
            probes=None if host_probes is None
            else jnp.asarray(host_probes),
        )
        ivf_ops.capture_launched()
        scores, ids = jax.device_get((scores, ids))
        # IVFFLAT scores are already exact — no rerank needed; cosine
        # similarity needs the query-norm correction only for reporting,
        # which normalization already handled.
        return self._pad_to_k(scores, ids, k)

    def reconstruction_error(self, sample: int = 256,
                             seed: int = 0) -> float | None:
        # buckets hold the raw vectors (store_dtype): scoring is exact,
        # so the quantization-drift gauge is identically zero
        return 0.0 if self.trained else None


@register_index("IVFPQ")
class IVFPQIndex(_IVFBase):
    """Realtime IVFPQ with residual encoding + exact rerank (reference:
    gamma_index_ivfpq.cc; rerank via raw vectors as in the reference's
    fine-grained reranking).

    Two device scan modes (param `scan_mode`, default "auto"):
    - "full": docid-ordered int8 compressed full scan (one MXU matmul) —
      realtime-friendly (appends, no publish rebuild) and the fastest
      path up to ~10M rows/chip;
    - "probe": bucket-grouped nprobe scan (compute scales with nprobe,
      for capacity-bound deployments);
    "auto" = full while the row count fits `full_scan_limit` (16M).
    """

    def __init__(self, params: IndexParams, store: RawVectorStore):
        super().__init__(params, store)
        self.m = int(params.get("nsubvector", params.get("m", 16)))
        if store.dimension % self.m != 0:
            # fail at create-table time, not in the background build thread
            raise ValueError(
                f"IVFPQ nsubvector={self.m} must divide dimension="
                f"{store.dimension}"
            )
        self.ksub = 1 << int(params.get("nbits_per_idx", params.get("nbits", 8)))
        # optional learned rotation before PQ (reference: OPQ option)
        self.opq = bool(params.get("opq", False))
        self.opq_iters = int(params.get("opq_iters", 5))
        self._opq_R: np.ndarray | None = None  # [d, d] orthonormal
        self.scan_mode = str(params.get("scan_mode", "auto"))
        self.full_scan_limit = int(params.get("full_scan_limit", 16_000_000))
        # one partition spanning the whole device mesh (capacity regime:
        # rows beyond a single chip's HBM — SURVEY §2.3 "intra-node
        # parallelism", the axis the reference lacks). Config
        # `mesh_serving: auto|on|off`; "auto" — the default — engages
        # whenever more than one device is visible.
        self.mesh_serving = self._norm_mesh_serving(
            params.get("mesh_serving", "auto")
        )
        # row -> cluster assignment, docid-ordered (the mesh probe gate
        # reads it row-sharded in lockstep with the int8 mirror)
        self._assign_host = np.zeros(0, dtype=np.int32)
        self._assign_cache = None
        self.codebooks: jax.Array | None = None  # [m, ksub, dsub]
        self._codes: np.ndarray | None = None  # [n_indexed, m] host codes
        # probe-mode state (bucket-grouped)
        self._bucket_resid8: jax.Array | None = None
        self._bucket_scale: jax.Array | None = None
        self._bucket_vsq: jax.Array | None = None
        # full-scan-mode state (docid-ordered compressed mirror,
        # append-only). mirror_dtype "int4" halves resident HBM per row
        # (the capacity knob for the full-scan regime).
        self.mirror_storage = str(
            params.get("mirror_dtype", "int8")
        ).lower()
        self._mirror = Int8Mirror(store.dimension,
                                  storage=self.mirror_storage)
        # full-scan dispatches: {site tag: {select_width: dispatches}}
        self._select: dict[str, dict[int, int]] = {}
        self._select_lock = lockcheck.make_lock("index_select_info")

    def _note_full_scan(self, tag: str, r: int, n_pad: int) -> None:
        """`note_dispatch` of a full-scan site, which hands `_select_topk`
        an [B, n_pad] score matrix at depth r: the dispatch carries, and
        `select_info` counts, how wide the widest sort of that selection
        is (`perf_model.select_width`: fixed by the shapes the bucket's
        program is compiled for, no property of the data)."""
        width = perf_model.select_width(r, n_pad)
        with self._select_lock:
            by_width = self._select.setdefault(tag, {})
            by_width[width] = by_width.get(width, 0) + 1
        ivf_ops.note_dispatch(tag, select_width=width)

    def select_info(self) -> dict[str, Any] | None:
        """{site tag: {select_width: dispatches}} of the full-scan
        dispatches so far; None before the first."""
        with self._select_lock:
            return {tag: {str(w): n for w, n in sorted(by_width.items())}
                    for tag, by_width in self._select.items()} or None

    @staticmethod
    def _norm_mesh_serving(value) -> str:
        ms = {True: "on", False: "off"}.get(value, str(value).lower())
        if ms in ("true", "1"):
            ms = "on"
        elif ms in ("false", "0", "none"):
            ms = "off"
        if ms not in ("auto", "on", "off"):
            raise ValueError(f"mesh_serving must be auto|on|off, got {value!r}")
        return ms

    def _mesh_enabled(self, params: dict | None) -> bool:
        """Whether this search serves through the device mesh. Read per
        request so apply_config({"index_params": {"mesh_serving": ...}})
        and per-request overrides both take effect without a rebuild."""
        ms = self._norm_mesh_serving(
            (params or {}).get(
                "mesh_serving", self.params.get("mesh_serving", "auto")
            )
        )
        if ms == "auto":
            return len(jax.devices()) > 1
        return ms == "on"

    def _serving_path(self, params: dict | None) -> str:
        """The documented path (ops/perf_model.py DOCUMENTED_DISPATCHES
        key) that serves this search; the one reader of the selectors,
        request level over index level. A disk store cannot hand a
        device program its raw rows, sharded or whole, so it neither
        meshes nor fuses; SCANN reordering=false wants no rerank. The
        full-scan budget is per chip: a mesh scans its rows in
        parallel, so its cliff scales with the DATA axis (a
        query_axis>1 mesh still holds n/data_axis rows a chip)."""
        from vearch_tpu.index._store_paths import is_disk_store

        disk = is_disk_store(self.store)
        rerank = self._exact_rerank_enabled(params)
        mesh = self._mesh_enabled(params) and not disk
        mode = (params or {}).get("scan_mode", self.scan_mode)
        if mode == "auto":
            limit = self.full_scan_limit
            if mesh:
                limit *= max(
                    int(self._serving_mesh(params).shape["data"]), 1
                )
            mode = "full" if self.indexed_count <= limit else "probe"
        if mesh and mode == "full":
            return "ivfpq_mesh_fused" if rerank else "ivfpq_mesh_scan"
        if mesh and rerank:
            # past the cliff a mesh partition keeps its row-sharded
            # layout and gates the one program to the probed cells
            return "ivfpq_mesh_probe"
        if mode != "full":
            return "ivfpq_probe"
        if rerank and not disk:
            return "ivfpq_full_fused"
        return "ivfpq_full_unfused"

    def _device_state_arrays(self) -> tuple:
        return super()._device_state_arrays() + (
            self.codebooks, self._bucket_resid8,
            self._bucket_scale, self._bucket_vsq,
        )

    def device_footprint_bytes(self) -> int:
        # bucket/centroid state + raw rerank store (super) + the
        # docid-ordered compressed mirror the full-scan mode serves from
        return super().device_footprint_bytes() + self._mirror.device_bytes()

    def _train_extra(self, sample: np.ndarray) -> None:
        assign = np.asarray(
            km.assign_clusters(jnp.asarray(sample), self.centroids)
        )
        resid = sample - np.asarray(self.centroids)[assign]
        if self.opq:
            # OPQ (reference: gamma_index_ivfpq.h opq_ option): learn an
            # orthonormal rotation R that decorrelates subvector energy,
            # by alternating PQ training on rotated residuals with the
            # Procrustes update R = UV^T from svd(X^T D(code(XR))).
            # Downstream stays untouched: codes live in rotated space,
            # the int8 mirror stores approximations rotated BACK to the
            # original space, so scan + rerank never see R. On TPU the
            # rotation is one [d, d] matmul folded into absorb.
            d = resid.shape[1]
            R = np.eye(d, dtype=np.float32)
            for _ in range(self.opq_iters):
                z = resid @ R
                self.codebooks = pq_ops.train_pq(
                    jnp.asarray(z), m=self.m, ksub=self.ksub,
                    iters=max(self.train_iters // 2, 2),
                )
                codes = np.asarray(
                    pq_ops.encode_pq(jnp.asarray(z), self.codebooks)
                )
                decoded = pq_ops.decode_pq_np(codes, self.codebooks)
                u, _s, vt = np.linalg.svd(resid.T @ decoded)
                R = (u @ vt).astype(np.float32)
            self._opq_R = R
            resid = resid @ R
        self.codebooks = self._fit_codebooks(resid, sample)
        self._codes = np.zeros((0, self.m), dtype=np.uint8)

    def _fit_codebooks(
        self, resid: np.ndarray, sample: np.ndarray
    ) -> jax.Array:
        """Codebook trainer hook — SCANN overrides this with the
        anisotropic (score-aware) trainer; `sample` is the original
        (pre-residual) rows it needs for the parallel direction."""
        return pq_ops.train_pq(
            jnp.asarray(resid), m=self.m, ksub=self.ksub,
            iters=self.train_iters,
        )

    def _encode_rows(self, resid: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Encoder hook (same override seam as `_fit_codebooks`)."""
        return _bulk_rows(
            lambda x: pq_ops.encode_pq(x, self.codebooks), resid)

    def _absorb_rows(
        self, rows: np.ndarray, assign: np.ndarray, start_docid: int
    ) -> None:
        cents = np.asarray(self.centroids)
        resid = rows - cents[assign]
        if self._opq_R is not None:
            resid = resid @ self._opq_R  # encode in rotated space
        codes = self._encode_rows(resid, rows)
        if self._codes is None:
            self._codes = np.zeros((0, self.m), dtype=np.uint8)
        need = start_docid + rows.shape[0]
        if self._codes.shape[0] < need:
            grown = np.zeros((max(need, self._codes.shape[0] * 2), self.m),
                             dtype=np.uint8)
            grown[: self._codes.shape[0]] = self._codes
            self._codes = grown
        self._codes[start_docid : start_docid + rows.shape[0]] = codes
        if self._assign_host.shape[0] < need:
            ga = np.zeros(max(need, self._assign_host.shape[0] * 2),
                          dtype=np.int32)
            ga[: self._assign_host.shape[0]] = self._assign_host
            self._assign_host = ga
        self._assign_host[start_docid:need] = assign.astype(np.int32)
        if self._assign_cache is not None:
            self._assign_cache.lower_rows(start_docid)

        # docid-ordered int8 mirror for the full-scan path: decode the PQ
        # approximation, rotate back to the original space (OPQ), add the
        # centroid, quantize per-row, append
        decoded = pq_ops.decode_pq_np(codes, self.codebooks)
        if self._opq_R is not None:
            decoded = decoded @ self._opq_R.T
        approx = cents[assign] + decoded
        if self.metric is MetricType.COSINE:
            # re-normalize the approximation: rows were normalized
            # before encoding, but PQ error perturbs the norm, and the
            # IP scan would rank by (1 ± err) * cos — on norm-spread
            # data (glove-like regime) that bias alone cost r@100
            # 0.465 -> the candidate set was norm-noise, not angle
            approx = approx / np.maximum(
                np.linalg.norm(approx, axis=1, keepdims=True), 1e-12)
        self._mirror.append(approx, start=start_docid)

    def _publish(self) -> None:
        """Decode PQ codes -> residual approximations -> int8 buckets.

        The decode+quantize runs once per publish (numpy, ~1s/M rows);
        searches then scan pure int8 matmuls (see ops/ivf.py design note).
        """
        with self._absorb_lock:
            self._publish_locked()

    def _publish_locked(self) -> None:
        publish = ivf_ops.phase("ivf.publish")
        ids = self._publish_ids()
        cap = ids.shape[1]
        d = self.store.dimension
        cents = np.asarray(self.centroids)
        dsub = d // self.m
        resid8 = np.zeros((self.nlist, cap, d), dtype=np.int8)
        scales = np.ones(self.nlist, dtype=np.float32)
        vsq = np.zeros((self.nlist, cap), dtype=np.float32)
        for c, mm in enumerate(self._members):
            if not mm:
                continue
            rows = np.asarray(mm, dtype=np.int64)
            codes = self._codes[rows]  # [nc, m]
            decoded = pq_ops.decode_pq_np(codes, self.codebooks)
            if self._opq_R is not None:
                decoded = decoded @ self._opq_R.T  # back to original space
            if self.metric is MetricType.COSINE:
                # same re-normalization as the mirror path (review r5):
                # redefine the residual against the NORMALIZED
                # approximation so the probe scan's cent_c + s*r8
                # decomposition reconstructs a unit-norm vector — PQ
                # norm error must not rank cosine candidates
                full = cents[c][None, :] + decoded
                full /= np.maximum(
                    np.linalg.norm(full, axis=1, keepdims=True), 1e-12)
                decoded = full - cents[c][None, :]
            scale = max(float(np.abs(decoded).max()) / 127.0, 1e-12)
            q8 = np.clip(np.rint(decoded / scale), -127, 127).astype(np.int8)
            approx = cents[c][None, :] + scale * q8.astype(np.float32)
            resid8[c, : len(mm)] = q8
            scales[c] = scale
            vsq[c, : len(mm)] = np.sum(approx * approx, axis=1)
        self._bucket_resid8 = jnp.asarray(resid8)
        self._bucket_scale = jnp.asarray(scales)
        self._bucket_vsq = jnp.asarray(vsq)
        self._dirty = False
        self._note_publish(publish, self._bucket_resid8, self._bucket_scale,
                           self._bucket_vsq, self._bucket_ids)

    def reconstruction_error(self, sample: int = 256,
                             seed: int = 0) -> float | None:
        """Decode the STORED codes (the serving representation) back to
        full vectors and compare against the raw store — host numpy
        only, no device dispatch. Covers SCANN too (same stored-code
        layout; the anisotropic encoder only changes which codes were
        chosen, not how they decode)."""
        with self._absorb_lock:
            n = int(self.indexed_count)
            if not self.trained or n == 0 or self._codes is None:
                return None
            rng = np.random.default_rng(seed)
            ids = np.sort(rng.choice(n, size=min(int(sample), n),
                                     replace=False))
            raw = self._maybe_normalize(
                np.asarray(self.store.host_view()[ids], dtype=np.float32)
            )
            decoded = pq_ops.decode_pq_np(self._codes[ids], self.codebooks)
            if self._opq_R is not None:
                decoded = decoded @ self._opq_R.T
            cents = np.asarray(self.centroids)
            approx = cents[self._assign_host[ids]] + decoded
            if self.metric is MetricType.COSINE:
                approx = approx / np.maximum(
                    np.linalg.norm(approx, axis=1, keepdims=True), 1e-12)
            num = np.linalg.norm(raw - approx, axis=1)
            den = np.maximum(np.linalg.norm(raw, axis=1), 1e-12)
            return float(np.mean(num / den))

    def search(
        self,
        queries: np.ndarray,
        k: int,
        valid_mask: np.ndarray | None,
        params: dict | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        assert self.trained, "IVFPQ search before training"
        q = self._maybe_normalize(np.asarray(queries, np.float32))
        metric = (
            MetricType.INNER_PRODUCT
            if self.metric is MetricType.COSINE
            else self.metric
        )
        path = self._serving_path(params)
        if path.startswith("ivfpq_mesh"):
            return self._search_mesh(q, k, valid_mask, params, metric, path)
        if path != "ivfpq_probe":
            approx8, scale, vsq = self._mirror.flush()
            n_pad = approx8.shape[0]
            valid = to_device_mask(valid_mask, self.indexed_count, n_pad)
            r = min(self._rerank_depth(k, params), max(self.indexed_count, 1))
            if path == "ivfpq_full_fused":
                # default hot path: scan + rerank as ONE device program
                # (two dispatches paid launch latency twice and
                # round-tripped nothing for it); its rerank gathers rows
                base, base_sqnorm, _ = self.store.device_buffer(packed=True)
                self._note_full_scan("fused_scan_rerank", max(r, k), n_pad)
                scores, ids = ivf_ops.int8_scan_rerank(
                    jnp.asarray(q), approx8, scale, vsq, valid,
                    base, base_sqnorm, max(r, k), k,
                    scan_metric=metric, rerank_metric=self.metric,
                    storage=self.mirror_storage,
                )
                ivf_ops.capture_launched()
                scores, ids = jax.device_get((scores, ids))
                return self._pad_to_k(scores, ids, k)
            scan = (
                ivf_ops.int8_scan_candidates
                if self.mirror_storage == "int8"
                else ivf_ops.int4_scan_candidates
            )
            self._note_full_scan("scan", max(r, k), n_pad)
            cand_s, cand_i = scan(
                jnp.asarray(q), approx8, scale, vsq, valid,
                max(r, k), metric,
            )
            ivf_ops.capture_launched()
        else:
            if self._dirty or self._bucket_resid8 is None:
                self._publish()
            nprobe = self._nprobe(params)
            r = min(self._rerank_depth(k, params), self._cap * nprobe, 2048)
            valid = self._valid_device(valid_mask, self.store.count)
            # pallas only pays off compiled; off-TPU the interpret-mode
            # kernel would be drastically slower than the XLA scan
            default_kernel = (
                "pallas" if jax.default_backend() == "tpu" else "xla"
            )
            kernel = (params or {}).get(
                "probe_kernel", self.params.get("probe_kernel", default_kernel)
            )
            host_probes = self._host_probes(q, nprobe)
            if host_probes is not None:
                # the pallas kernel selects probes in-kernel via scalar
                # prefetch; host-graph selection rides the XLA path
                kernel = "xla"
            ivf_ops.note_dispatch("probe_scan", kernel=kernel)
            if kernel == "pallas":
                from vearch_tpu.ops.pallas_kernels import (
                    ivfpq_probe_search_pallas,
                )

                cand_s, cand_i = ivfpq_probe_search_pallas(
                    jnp.asarray(q),
                    self.centroids,
                    self._bucket_resid8,
                    self._bucket_scale,
                    self._bucket_vsq,
                    self._bucket_ids,
                    valid,
                    nprobe,
                    max(r, k),
                    metric is MetricType.L2,
                )
            else:
                cand_s, cand_i = ivf_ops.ivfpq_candidates(
                    jnp.asarray(q),
                    self.centroids,
                    self._bucket_resid8,
                    self._bucket_scale,
                    self._bucket_vsq,
                    self._bucket_ids,
                    valid,
                    nprobe,
                    max(r, k),
                    metric,
                    probes=None if host_probes is None
                    else jnp.asarray(host_probes),
                )
            ivf_ops.capture_launched()
        if not self._exact_rerank_enabled(params):
            # SCANN reordering=false: pure quantized scores, no raw-store
            # gather (candidates come out of the scan best-first)
            scores, ids = jax.device_get((cand_s, cand_i))
            return self._pad_to_k(scores[:, :k], ids[:, :k], k)
        from vearch_tpu.index._store_paths import rerank_against_store

        ivf_ops.note_dispatch("rerank")
        scores, ids = rerank_against_store(
            self.store, q, cand_i, min(k, int(cand_i.shape[1])), self.metric,
        )
        scores, ids = jax.device_get((scores, ids))
        return self._pad_to_k(scores, ids, k)

    def _mesh_nprobe(self, params: dict | None) -> int:
        """Coarse-probe gate depth of the mesh program (0 = ungated full
        scan). Unlike single-device "probe" mode this gates the docid-
        ordered mirror inside the one fused program instead of switching
        to the bucket-grouped layout."""
        p = params or {}
        return min(
            int(p.get("mesh_nprobe", self.params.get("mesh_nprobe", 0))),
            self.nlist,
        )

    def _mesh_valid_sharded(self, mesh, valid_mask, n: int, cap: int):
        """Sharded validity mask, cached per source-mask identity.

        The sharded mask re-uploads only when the engine handed us a
        different mask object (the engine caches its alive mask per
        bitmap version; filter masks are fresh arrays by nature). The
        strong reference to the source mask makes the identity check
        sound — a live object's id cannot be reused."""
        from vearch_tpu.parallel import mesh as mesh_lib

        fresh = not (
            getattr(self, "_mesh_valid_src", None) is valid_mask
            and valid_mask is not None
            and getattr(self, "_mesh_valid_n", -1) == n
            and getattr(self, "_mesh_valid_cap", -1) == cap
        )
        if fresh:
            host_valid = np.zeros(cap, dtype=bool)
            if valid_mask is None:
                host_valid[:n] = True
            else:
                vm = np.asarray(valid_mask)[:n]
                host_valid[: vm.shape[0]] = vm
            self._mesh_valid, _ = mesh_lib.shard_rows(mesh, host_valid)
            self._mesh_valid_src = valid_mask
            self._mesh_valid_n = n
            self._mesh_valid_cap = cap
        return self._mesh_valid

    def _assign_sharded(self, mesh, n: int):
        """Row->cluster assignment sharded in lockstep with the mirror
        (same 512 alignment, so local row offsets line up per shard)."""
        if self._assign_cache is None:
            from vearch_tpu.parallel.mesh import ShardedRowCache

            self._assign_cache = ShardedRowCache(align=512)

        def build(cap):
            host = np.zeros(cap, dtype=np.int32)
            host[:n] = self._assign_host[:n]
            return (host,)

        def append(lo, hi):
            return (np.ascontiguousarray(self._assign_host[lo:hi]),)

        (assign,), _ = self._assign_cache.get(mesh, n, build, append)
        return assign

    def _search_mesh(
        self, q: np.ndarray, k: int, valid_mask, params, metric,
        path: str,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Mesh-spanning serving path: the int8 mirror, the raw rerank
        buffer, and the row->cluster assignment are row-sharded over the
        serving mesh's "data" axis, the query batch shards over its
        "query" axis; an optional coarse-probe gate, the compressed
        scan, the all_gather candidate merge, the exact rerank, and the
        pmax score merge all run inside ONE jitted shard_map program —
        no host round trips (reference analogue: none; this is the TPU
        capacity axis on top of the reference's partition sharding).
        Placement is incremental: absorb tail-appends only the new rows
        per shard.

        ``path`` is `_serving_path`'s: "ivfpq_mesh_probe" is the probe
        REGIME (same fused program, gated to the probed cells, a
        dispatch tag of its own); "ivfpq_mesh_scan" wants no rerank."""
        from vearch_tpu.parallel import mesh as mesh_lib
        from vearch_tpu.parallel.sharded import (
            sharded_int8_search,
            sharded_ivf_search,
        )

        place = ivf_ops.phase("mesh.place", request_only=True)
        h2d0 = perf_model.h2d_bytes_total()

        def note_place(**tags) -> None:
            # `bytes`: what the process uploaded during the phase: the
            # query batch on every request; a re-placement or a
            # tail-append of mirror, raw store or mask shows as more
            place.close({"bytes": perf_model.h2d_bytes_total() - h2d0,
                         **tags})

        mesh = self._serving_mesh(params)
        a8, scale, vsq = self._mirror.flush_sharded(mesh)
        n = self.indexed_count
        cap = self._mirror._sh_cache.capacity(mesh, n)
        valid_sh = self._mesh_valid_sharded(mesh, valid_mask, n, cap)
        probe = path == "ivfpq_mesh_probe"
        nprobe = (max(self._nprobe(params), 1) if probe
                  else self._mesh_nprobe(params))
        cents = assign_sh = None
        if nprobe > 0:
            cents = mesh_lib.replicate(mesh, np.asarray(self.centroids))
            assign_sh = self._assign_sharded(mesh, n)
        qd, b = mesh_lib.shard_queries(mesh, np.asarray(q, np.float32))
        r = min(self._rerank_depth(k, params), max(n, 1))
        local_n = cap // int(mesh.shape["data"])  # a shard's rows
        if path != "ivfpq_mesh_scan":
            base, base_sqn, _ = self.store.device_buffer_sharded(mesh)
            # rows a device row of the raw shard as placed (row_pack)
            note_place(raw_pack=base.shape[1] // qd.shape[1])
            self._note_full_scan(
                "sharded_probe_scan_rerank" if probe
                else "sharded_fused_scan_rerank", max(r, k), local_n)
            scores, ids = sharded_ivf_search(
                mesh, cents, assign_sh, a8, scale, vsq, valid_sh,
                base, base_sqn, qd, max(r, k),
                min(k, max(r, k)),
                scan_metric=metric, rerank_metric=self.metric,
                storage=self.mirror_storage, nprobe=nprobe,
            )
            ivf_ops.capture_launched()
            scores, ids = jax.device_get((scores, ids))
            return self._pad_to_k(scores[:b], ids[:b], k)
        note_place()
        self._note_full_scan("sharded_scan", max(r, k), local_n)
        cand_s, cand_i = sharded_int8_search(
            mesh, a8, scale, vsq, valid_sh, qd, max(r, k), metric,
            storage=self.mirror_storage,
        )
        ivf_ops.capture_launched()
        scores, ids = jax.device_get((cand_s, cand_i))
        return self._pad_to_k(scores[:b, :k], ids[:b, :k], k)

    def mesh_info(self) -> dict[str, Any] | None:
        """Mesh data-plane placement summary (surfaced in /ps/stats and
        profile:true explains); None when mesh serving is off."""
        if not self._mesh_enabled(None):
            return None
        mesh = self._serving_mesh(None)
        sh = self._mirror._sh_cache
        info: dict[str, Any] = {
            "devices": int(mesh.size),
            "data_shards": int(mesh.shape["data"]),
            "query_shards": int(mesh.shape["query"]),
            "per_device_bytes": self.device_footprint_per_device_bytes(),
        }
        if sh is not None:
            info["mirror_placement"] = dict(sh.stats)
        rs = getattr(self.store, "_sh_cache", None)
        if rs is not None:
            info["raw_placement"] = {**rs.stats, "row_pack": rs.pack}
        return info

    def device_footprint_per_device_bytes(self) -> int:
        """Per-device resident HBM model of mesh serving: row-sharded
        state (mirror, raw base, assignment) divides by the shard count;
        replicated state (centroids, bucket tensors when published)
        rides whole on every chip (ops/perf_model.per_device_bytes)."""
        if not self._mesh_enabled(None):
            return self.device_footprint_bytes()
        mesh = self._serving_mesh(None)
        n_shards = int(mesh.shape["data"])
        sharded = self._mirror.device_bytes() + \
            perf_model.raw_store_footprint_bytes(
                self.store.capacity, self.store.dimension,
                self.store.store_dtype.itemsize,
            ) + self._assign_host.shape[0] * 4
        replicated = 0
        for a in self._device_state_arrays():
            if a is not None:
                replicated += int(a.size) * a.dtype.itemsize
        return perf_model.per_device_bytes(sharded, replicated, n_shards)

    def dump_state(self) -> dict[str, Any]:
        state = super().dump_state()
        if state and self.codebooks is not None:
            state["codebooks"] = np.asarray(self.codebooks)
            if self._opq_R is not None:
                state["opq_R"] = self._opq_R
        return state

    def _load_codebooks(self, state: dict[str, Any]) -> None:
        self.codebooks = jnp.asarray(state["codebooks"])
        if "opq_R" in state:
            self._opq_R = np.asarray(state["opq_R"], dtype=np.float32)
        self._codes = np.zeros((0, self.m), dtype=np.uint8)
