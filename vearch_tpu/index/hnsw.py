"""HNSW index type — TPU-native interpretation with a real graph tier.

The reference vendors hnswlib (reference: index/impl/hnswlib/
gamma_index_hnswlib.cc:130). Two serving modes live behind the one
index type (param `graph`, default "auto"):

- **scan** (TPU default): pointer-chasing graph walks are wrong for the
  MXU; at any N that fits a chip, a two-stage device scan (int8 coarse
  pass + exact rerank of the top `efSearch`) beats a graph walk while
  preserving HNSW's contract (approximate, efSearch recall knob,
  realtime inserts, deletes honored).
- **graph**: an actual host-side HNSW graph (csrc/vearch_hnsw.cpp — an
  independent implementation of Malkov & Yashunin 2016, not vendored
  hnswlib), for the regimes a scan can't serve: beyond-HBM row counts
  (pairs with DiskRawVectorStore: the graph owns its own host copy) and
  single-query low-latency paths with no device round-trip.

"auto" = graph when the raw store is disk-resident and the native
toolchain is present, else scan. `graph: true` forces the graph (errors
without a toolchain); `graph: false` forces the scan.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from vearch_tpu.engine.raw_vector import RawVectorStore
from vearch_tpu.engine.types import IndexParams, MetricType
from vearch_tpu.index._store_paths import is_disk_store, rerank_against_store
from vearch_tpu.index.base import VectorIndex
from vearch_tpu.index.int8_mirror import ABSORB_ROWS, Int8Mirror
from vearch_tpu.index.registry import register_index
from vearch_tpu.ops import ivf as ivf_ops
from vearch_tpu.ops import perf_model
from vearch_tpu.ops.distance import to_device_mask


@register_index("HNSW")
class HNSWIndex(VectorIndex):
    needs_training = False

    def __init__(self, params: IndexParams, store: RawVectorStore):
        super().__init__(params, store)
        self.ef_search = int(params.get("efSearch", params.get("ef_search", 64)))
        self.m = int(params.get("nlinks", params.get("M", 16)))
        self.ef_construction = int(
            params.get("efConstruction", params.get("ef_construction", 200))
        )
        self._mirror = Int8Mirror(store.dimension)
        self._graph = None
        mode = params.get("graph", "auto")
        if mode == "auto":
            from vearch_tpu.native import hnsw_graph

            self.use_graph = is_disk_store(store) and hnsw_graph.available()
        else:
            self.use_graph = bool(mode)
        if self.use_graph:
            from vearch_tpu.native.hnsw_graph import HnswGraph

            self._graph = HnswGraph(
                store.dimension, m=self.m,
                ef_construction=self.ef_construction,
                ip=self.metric is not MetricType.L2,
            )

    def _maybe_normalize(self, x: np.ndarray) -> np.ndarray:
        if self.metric is MetricType.COSINE:
            n = np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-15)
            return (x / n).astype(np.float32)
        return x

    def absorb(self, upto: int) -> None:
        with self._absorb_lock:
            if upto <= self.indexed_count:
                return
            start = self.indexed_count
            host = self.store.host_view()
            if self._graph is not None:
                self._graph.add(self._maybe_normalize(
                    np.asarray(host[start:upto], dtype=np.float32)))
            else:
                # in pieces: after a restore the first search absorbs
                # every row, and a whole normalised copy of a million
                # 960-d rows is 3.84 GB before quantize_rows' temporaries
                self._mirror.reserve(upto)
                for lo in range(start, upto, ABSORB_ROWS):
                    hi = min(lo + ABSORB_ROWS, upto)
                    self._mirror.append(self._maybe_normalize(
                        np.asarray(host[lo:hi], dtype=np.float32)), start=lo)
            self.indexed_count = upto

    def search(
        self,
        queries: np.ndarray,
        k: int,
        valid_mask: np.ndarray | None,
        params: dict | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        p = params or {}
        ef = max(int(p.get("efSearch", p.get("ef_search", self.ef_search))), k)
        if self._graph is not None:
            self.absorb(self.store.count)
            q = self._maybe_normalize(np.asarray(queries, np.float32))
            return self._search_graph(q, k, ef, valid_mask)
        return self._search_scan(queries, k, ef, valid_mask)

    def _search_graph(
        self, q: np.ndarray, k: int, ef: int, valid_mask
    ) -> tuple[np.ndarray, np.ndarray]:
        mask = None
        n = self._graph.count
        if valid_mask is not None:
            mask = np.asarray(valid_mask, dtype=np.uint8)
            if mask.shape[0] < n:
                mask = np.pad(mask, (0, n - mask.shape[0]))
        elif n > self.indexed_count:
            # a crash-rollback load can leave phantom graph nodes past
            # the durable count; mask them out rather than serving them
            mask = np.zeros(n, dtype=np.uint8)
            mask[: self.indexed_count] = 1
        scores, ids = self._graph.search(q, k, ef, mask)
        # graph distances are exact f32 (the graph owns full-precision
        # rows), so scores are final: -L2^2, or dot on normalized rows
        return scores, ids.astype(np.int64)

    def _search_scan(
        self, queries: np.ndarray, k: int, ef: int, valid_mask
    ) -> tuple[np.ndarray, np.ndarray]:
        """Every row scored from its int8 form, the top `ef` reranked
        exactly against the raw rows: ONE device program for a RAM store
        (`int8_scan_rerank`, which gathers the `ef` rows from the store
        as placed for a gather); a disk store's rows are gathered on the
        host between a scan and a rerank program."""
        # a RAM store's place phase: from the index's entry to the launch
        # (the tail absorb, the mirror's flush, the raw store's placement,
        # the mask, the query's normalisation and upload)
        place = ivf_ops.phase("hnsw.place", request_only=True)
        self.absorb(self.store.count)
        qh = self._maybe_normalize(np.asarray(queries, np.float32))
        q = jnp.asarray(qh)
        a8, scale, vsq = self._mirror.flush()
        n_pad = a8.shape[0]
        # cosine scans as IP over the normalised rows; the rerank scores
        # the user-facing metric against the rows as stored
        metric = (
            MetricType.INNER_PRODUCT
            if self.metric is MetricType.COSINE
            else self.metric
        )
        valid = to_device_mask(valid_mask, self.indexed_count, n_pad)
        r = min(ef, max(self.indexed_count, 1))
        if is_disk_store(self.store):
            ivf_ops.note_dispatch("scan")
            _, cand_i = ivf_ops.int8_scan_candidates(
                q, a8, scale, vsq, valid, r, metric)
            ivf_ops.note_dispatch("rerank")
            scores, ids = rerank_against_store(
                self.store, qh, cand_i, k, self.metric)
        else:
            base, base_sqnorm, _ = self.store.device_buffer(packed=True)
            place.close({"ef": r, "rows": self.indexed_count,
                         "mirror_bytes": self._mirror.placed_bytes(),
                         "store_pack": base.shape[1] // self.store.dimension})
            ivf_ops.note_dispatch(
                "fused_scan_rerank",
                select_width=perf_model.select_width(r, n_pad))
            scores, ids = ivf_ops.int8_scan_rerank(
                q, a8, scale, vsq, valid, base, base_sqnorm, r, k,
                scan_metric=metric, rerank_metric=self.metric,
            )
            ivf_ops.capture_launched()
        scores, ids = jax.device_get((scores, ids))
        if scores.shape[1] < k:
            pad = k - scores.shape[1]
            scores = np.pad(scores, ((0, 0), (0, pad)),
                            constant_values=float("-inf"))
            ids = np.pad(ids, ((0, 0), (0, pad)), constant_values=-1)
        return scores[:, :k], ids[:, :k]

    # -- persistence ---------------------------------------------------------

    def dump_state(self) -> dict[str, Any]:
        if self._graph is None or self._graph.count == 0:
            return {}
        import os
        import tempfile

        fd, tmp = tempfile.mkstemp(suffix=".hnsw")
        os.close(fd)
        try:
            self._graph.save(tmp)
            with open(tmp, "rb") as f:
                blob = np.frombuffer(f.read(), dtype=np.uint8)
        finally:
            os.unlink(tmp)
        return {
            "graph_blob": blob,
            "indexed_count": np.int64(self.indexed_count),
        }

    def load_state(self, state: dict[str, Any]) -> None:
        if "graph_blob" not in state or self._graph is None:
            # scan mode re-absorbs from raw vectors on demand
            return
        import os
        import tempfile

        from vearch_tpu.native.hnsw_graph import HnswGraph

        fd, tmp = tempfile.mkstemp(suffix=".hnsw")
        os.close(fd)
        try:
            with open(tmp, "wb") as f:
                f.write(np.asarray(state["graph_blob"]).tobytes())
            self._graph = HnswGraph.load(
                tmp, self.store.dimension, m=self.m,
                ef_construction=self.ef_construction,
                ip=self.metric is not MetricType.L2,
            )
        except ValueError:
            # corrupt blob: raw vectors are the durable source of truth
            # — fall through to the rebuild path below
            self._graph = HnswGraph(
                self.store.dimension, m=self.m,
                ef_construction=self.ef_construction,
                ip=self.metric is not MetricType.L2,
            )
        finally:
            os.unlink(tmp)
        saved = int(state.get("indexed_count", self._graph.count))
        if saved != self._graph.count or saved > self.store.count:
            # graph ids must stay == docids; any snapshot/store mismatch
            # (crash rollback) means appends would misalign — rebuild
            self._graph = HnswGraph(
                self.store.dimension, m=self.m,
                ef_construction=self.ef_construction,
                ip=self.metric is not MetricType.L2,
            )
            self.indexed_count = 0
        else:
            self.indexed_count = saved
        # tail rows past the snapshot re-absorb from the raw store
        self.absorb(self.store.count)
