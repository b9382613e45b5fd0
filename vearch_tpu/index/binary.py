"""BINARYIVF and IVFRABITQ index types.

BINARYIVF (reference: index/impl/gamma_index_binary_ivf.cc:62 — faiss
binary IVF with Hamming distance): binary vectors arrive packed as
`dimension/8` uint8 bytes. TPU-native trick: unpack bits to 0/1 floats,
then for bit vectors squared-L2 *is* Hamming distance
(`(a-b)^2 == |a-b|` for a,b in {0,1}), so the entire IVFFLAT machinery —
k-means coarse training, bucket scan on the MXU, deletion masking —
applies unchanged and the reported L2 score is the exact Hamming
distance. No XOR/popcount loops (VPU-serial); one matmul.

IVFRABITQ (reference: index/impl/gamma_index_ivfrabitq.cc:38 — faiss
RaBitQ 1-bit-per-dim quantization, estimator-then-rerank): served as a
progressive THREE-STAGE refinement chain. HBM holds two compressed
views of every row — packed sign-bit planes (1 bit/dim, the stage-0
tier; ops/binary_scan.py) and the int8 RaBitQ reconstruction
`centroid + scale * sign(resid)` (the stage-1 tier, shared Int8Mirror
layout) — while the raw base stays in the store (device buffer for RAM
stores, NVMe mmap for disk stores, where stage-2 gathers ride the
readahead path). A search runs binary scan -> top r0 -> int8 rescore
-> top r1 -> exact rerank -> top k; for a RAM store all three stages
fuse into ONE device program, and under a mesh the bit planes shard
row-wise in lockstep with the mirror (parallel/sharded.py
sharded_binary_refine). `r0`/`r1` are runtime-tunable (request params
or /ps/engine/config index_params) with perf-model auto-defaults
(ops/perf_model.refine_depths); `stage0: "off"` falls back to the
int8-only full-scan chain for A/B and recall-parity gating.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

from vearch_tpu.engine.raw_vector import RawVectorStore
from vearch_tpu.engine.types import IndexParams, MetricType
from vearch_tpu.index.int8_mirror import ABSORB_ROWS, Int8Mirror
from vearch_tpu.index.ivf import IVFFlatIndex, IVFPQIndex
from vearch_tpu.index.registry import register_index
from vearch_tpu.ops import binary_scan as binary_ops
from vearch_tpu.ops import ivf as ivf_ops
from vearch_tpu.ops import perf_model
from vearch_tpu.ops.distance import to_device_mask
from vearch_tpu.tools import lockcheck


@register_index("BINARYIVF")
class BinaryIVFIndex(IVFFlatIndex):
    """Hamming-metric IVF over packed binary vectors."""

    def __init__(self, params: IndexParams, store: RawVectorStore):
        if store.dimension % 8 != 0:
            raise ValueError(
                f"BINARYIVF dimension {store.dimension} must be a multiple of 8"
            )
        super().__init__(params, store)

    @property
    def input_dim(self) -> int:
        # wire format: dimension/8 packed bytes (reference: faiss binary)
        return self.store.dimension // 8

    def decode_input(self, batch: np.ndarray) -> np.ndarray:
        """[b, d/8] uint8 -> [b, d] 0/1 float32."""
        packed = np.asarray(batch, dtype=np.uint8)
        bits = np.unpackbits(packed, axis=1, count=self.store.dimension)
        return bits.astype(np.float32)


@register_index("IVFRABITQ")
class IVFRaBitQIndex(IVFPQIndex):
    """1-bit stage-0 tier + progressive three-stage refinement.

    Overrides the PQ codebook stages: there are no codebooks — rows
    store as packed sign planes (stage 0) and as the RaBitQ first-order
    reconstruction `centroid + mean|resid| * sign(resid)` quantized
    into the int8 mirror (stage 1). `nsubvector`/`nbits` are ignored.
    """

    def __init__(self, params: IndexParams, store: RawVectorStore):
        # bypass IVFPQ's m-divides-d validation: there are no subvectors
        params = IndexParams(
            index_type=params.index_type,
            metric_type=params.metric_type,
            params={**params.params, "nsubvector": 1},
        )
        super().__init__(params, store)
        # stage-0 tier: packed sign planes of the (normalized) rows,
        # same append/flush/shard machinery as the int8 mirror
        self._bits = Int8Mirror(store.dimension, storage="bits")
        #: what `refine_info` reports: fused three-stage searches served
        #: and the rows each stage scored for them, the last depths
        self._refine = {"searches": 0, "r0": 0, "r1": 0,
                        "stage_rows": dict.fromkeys(
                            binary_ops.REFINE_STAGES, 0)}
        self._refine_lock = lockcheck.make_lock("index_refine_info")

    def _train_extra(self, sample: np.ndarray) -> None:
        # no codebooks to train; only the coarse quantizer (in base train)
        self.codebooks = None
        self._codes = np.zeros((0, 1), dtype=np.uint8)

    def _absorb_rows(
        self, rows: np.ndarray, assign: np.ndarray, start_docid: int
    ) -> None:
        cents = np.asarray(self.centroids)
        # in pieces: every row's result is its own, and a piece's
        # [rows, d] f32 temporaries (gathered centroids, residual, its
        # magnitudes and signs, the reconstruction, the dequantised
        # copy) stay in the host's cache. Whole, a million 960-d rows
        # held the build's host at 40 of its 46 GB and its `assign`
        # phase read 100 s; in pieces 28-35 s (PERF.md section 6, PR 34)
        for tier in (self._mirror, self._bits):
            tier.reserve(start_docid + rows.shape[0])
        for lo in range(0, rows.shape[0], ABSORB_ROWS):
            piece, cent = rows[lo:lo + ABSORB_ROWS], cents[
                assign[lo:lo + ABSORB_ROWS]]
            resid = piece - cent
            scale = np.maximum(
                np.abs(resid).mean(axis=1), 1e-12
            ).astype(np.float32)
            recon = cent + scale[:, None] * np.sign(resid)
            self._mirror.append(recon.astype(np.float32),
                                start=start_docid + lo)
            # stage-0 bit planes quantize the ROW itself (not the
            # residual): the binary scan is partition-global, so its
            # estimator must not depend on a per-row centroid term the
            # kernel can't afford
            self._bits.append(piece, start=start_docid + lo)

    def device_footprint_bytes(self) -> int:
        return super().device_footprint_bytes() + self._bits.device_bytes()

    # -- three-stage serving ---------------------------------------------------

    def _stage0_enabled(self, params: dict | None) -> bool:
        mode = str((params or {}).get(
            "stage0", self.params.get("stage0", "binary")
        )).lower()
        if mode not in ("binary", "off"):
            raise ValueError(f"stage0 must be binary|off, got {mode!r}")
        return mode == "binary"

    def _stage_depths(self, k: int, params: dict | None) -> tuple[int, int]:
        """(r0, r1) candidate depths: request params win, then index
        params (runtime-tunable via /ps/engine/config index_params),
        then the perf model's documented auto-defaults."""
        p = params or {}
        n = max(self.indexed_count, 1)
        auto_r0, auto_r1 = perf_model.refine_depths(k, n)
        r1 = int(p.get("r1", p.get(
            "rerank", self.params.get(
                "r1", self.params.get("rerank", auto_r1))
        )))
        r0 = int(p.get("r0", self.params.get("r0", auto_r0)))
        r1 = min(max(r1, k), n)
        r0 = min(max(r0, r1), n)
        return r0, r1

    def search(self, queries, k, valid_mask, params=None):
        if not self._stage0_enabled(params):
            # A/B escape hatch + recall-parity baseline: the int8-only
            # full-scan chain (scan + exact rerank) over the stage-1
            # mirror, exactly the pre-stage-0 serving path
            p = dict(params or {})
            p["scan_mode"] = "full"
            return super().search(queries, k, valid_mask, p)
        assert self.trained, "IVFRABITQ search before training"
        from vearch_tpu.index._store_paths import is_disk_store

        q = self._maybe_normalize(np.asarray(queries, np.float32))
        metric = (
            MetricType.INNER_PRODUCT
            if self.metric is MetricType.COSINE
            else self.metric
        )
        r0, r1 = self._stage_depths(k, params)
        if self._mesh_enabled(params) and not is_disk_store(self.store):
            return self._search_binary_mesh(
                q, k, valid_mask, params, metric, r0, r1
            )
        place = ivf_ops.phase("refine.place", request_only=True)
        planes, p_scale, p_vsq = self._bits.flush()
        # stage 1 GATHERS its r0 rows: the payload as placed for that
        approx8, m_scale, m_vsq = self._mirror.flush(packed=True)
        n_pad = planes.shape[0]
        valid = to_device_mask(valid_mask, self.indexed_count, n_pad)
        import jax.numpy as jnp

        qd = jnp.asarray(q)
        if is_disk_store(self.store):
            # stages 0-1 on device, stage-2 rows host-gathered through
            # the mmap + coalesced-readahead path (tiering/readahead.py
            # via store.get_rows) — the raw base never enters HBM
            self._note_full_scan("binary_refine_scan", r0, n_pad)
            _, cand_i = binary_ops.binary_refine_candidates(
                qd, planes, p_scale, p_vsq, approx8, m_scale, m_vsq,
                valid, r0, r1, metric, self.mirror_storage,
            )
            cand_i.block_until_ready()
            from vearch_tpu.index._store_paths import rerank_against_store

            ivf_ops.note_dispatch("rerank")
            scores, ids = rerank_against_store(
                self.store, q, cand_i, min(k, int(cand_i.shape[1])),
                self.metric,
            )
            scores, ids = jax.device_get((scores, ids))
            binary_ops.note_refine_search(
                "disk", self.indexed_count, r0, r1, k, q.shape[0])
            return self._pad_to_k(scores, ids, k)
        base, base_sqnorm, _ = self.store.device_buffer(packed=True)
        # the place phase: from the index's entry to the launch (both
        # flushes, the raw store's, the mask, the query upload), with
        # what the program is about to funnel and read
        place.close(
            {"r0": r0, "r1": r1, "rows": self.indexed_count,
             "plane_bytes": self._bits.placed_bytes(),
             "mirror_bytes": self._mirror.placed_bytes()})
        self._note_full_scan("binary_refine_rerank", r0, n_pad)
        scores, ids = binary_ops.binary_refine_rerank(
            qd, planes, p_scale, p_vsq, approx8, m_scale, m_vsq, valid,
            base, base_sqnorm, r0, r1, k,
            scan_metric=metric, rerank_metric=self.metric,
            storage=self.mirror_storage,
        )
        ivf_ops.capture_launched()
        scores, ids = jax.device_get((scores, ids))
        self._note_refine(r0, r1, q.shape[0])
        binary_ops.note_refine_search(
            "fused", self.indexed_count, r0, r1, k, q.shape[0])
        return self._pad_to_k(scores, ids, k)

    def _note_refine(self, r0: int, r1: int, batch: int) -> None:
        with self._refine_lock:
            info = self._refine
            info["searches"] += 1
            info["r0"], info["r1"] = r0, r1
            for stage, rows in zip(binary_ops.REFINE_STAGES,
                                   (self.indexed_count, r0, r1)):
                info["stage_rows"][stage] += rows * batch

    def refine_info(self) -> dict[str, Any] | None:
        """The three-stage funnel as this index served it on one chip:
        searches, the rows each stage scored for them (every row of the
        partition, r0, r1, times the query rows), the last depths, and
        the device bytes of the three representations as placed; None
        before the first such search."""
        with self._refine_lock:
            if not self._refine["searches"]:
                return None
            info = {**self._refine,
                    "stage_rows": dict(self._refine["stage_rows"])}
        return {**info, "plane_bytes": self._bits.placed_bytes(),
                "mirror_bytes": self._mirror.placed_bytes(),
                "raw_bytes": self.store.placed_bytes()}

    def _search_binary_mesh(
        self, q: np.ndarray, k: int, valid_mask, params, metric,
        r0: int, r1: int,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Mesh-spanning three-stage chain: bit planes, int8 mirror,
        and raw base row-sharded in lockstep (identical ShardedRowCache
        alignment); stages 0-1 shard-local, one all_gather merge, pmax
        exact rerank — ONE shard_map program."""
        from vearch_tpu.parallel import mesh as mesh_lib
        from vearch_tpu.parallel.sharded import sharded_binary_refine

        place = ivf_ops.phase("mesh.place", request_only=True)
        mesh = self._serving_mesh(params)
        planes, p_scale, p_vsq = self._bits.flush_sharded(mesh)
        a8, m_scale, m_vsq = self._mirror.flush_sharded(mesh)
        n = self.indexed_count
        cap = self._bits._sh_cache.capacity(mesh, n)
        valid_sh = self._mesh_valid_sharded(mesh, valid_mask, n, cap)
        base, base_sqn, _ = self.store.device_buffer_sharded(mesh)
        qd, b = mesh_lib.shard_queries(mesh, np.asarray(q, np.float32))
        place.close()
        self._note_full_scan("sharded_binary_refine_rerank", r0,
                             cap // int(mesh.shape["data"]))
        scores, ids = sharded_binary_refine(
            mesh, planes, p_scale, p_vsq, a8, m_scale, m_vsq, valid_sh,
            base, base_sqn, qd, r0, r1, min(k, r1),
            scan_metric=metric, rerank_metric=self.metric,
            storage=self.mirror_storage,
        )
        scores, ids = jax.device_get((scores, ids))
        binary_ops.note_refine_search("mesh", n, r0, r1, k, b)
        return self._pad_to_k(scores[:b], ids[:b], k)

    def device_footprint_per_device_bytes(self) -> int:
        if not self._mesh_enabled(None):
            return self.device_footprint_bytes()
        # bit planes shard row-wise with the mirror: add their payload
        # to the sharded term of the IVFPQ per-device model
        base = super().device_footprint_per_device_bytes()
        mesh = self._serving_mesh(None)
        n_shards = int(mesh.shape["data"])
        return base + -(-self._bits.device_bytes() // max(n_shards, 1))

    def reconstruction_error(self, sample: int = 256,
                             seed: int = 0) -> float | None:
        """The STORED stage-1 rows (the int8 reconstruction, dequantised)
        against the raw rows; host numpy only. IVFPQ's reads PQ codes,
        of which this index has none (it raised on every sample of the
        quality monitor: `engine.quality_info` internal errors)."""
        with self._absorb_lock:
            n = min(int(self.indexed_count), self._mirror.count)
            if not self.trained or n == 0 or self.mirror_storage != "int8":
                return None
            ids = np.sort(np.random.default_rng(seed).choice(
                n, size=min(int(sample), n), replace=False))
            raw = self._maybe_normalize(
                np.asarray(self.store.host_view()[ids], dtype=np.float32))
            approx = (self._mirror._h8[ids].astype(np.float32)
                      * self._mirror._h_scale[ids, None])
            num = np.linalg.norm(raw - approx, axis=1)
            den = np.maximum(np.linalg.norm(raw, axis=1), 1e-12)
            return float(np.mean(num / den))

    def _publish(self) -> None:
        # probe mode unsupported for 1-bit codes; the stage-0/stage-1
        # mirrors (filled in _absorb_rows) are always used
        self._dirty = False

    def dump_state(self):
        state = super().dump_state()
        state.pop("codebooks", None)
        return state

    def _load_codebooks(self, state):
        self._codes = np.zeros((0, 1), dtype=np.uint8)
