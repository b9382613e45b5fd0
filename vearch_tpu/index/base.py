"""Pluggable vector index framework.

TPU-native re-design of the reference's IndexModel ABC + Reflector registry
(reference: internal/engine/index/index_model.h:236 `IndexModel`,
reflector.h:26,67 `REGISTER_INDEX`). The reference's GPU index types
(index/impl/gpu/) are the precedent: an accelerator backend behind the same
plugin seam. Here every index runs its dense math as jit'd JAX programs.

Contract differences from the reference, driven by TPU semantics:
- `add` is append-only with docid == row id; updates/deletes are handled
  by the engine's soft-delete bitmap, indexes never mutate rows in place;
- `search` takes a host validity mask (deletions + scalar filter) and must
  apply it *inside* the kernel (masked top-k), not post-filter, so k valid
  results survive;
- `train`/`build` may be called from a background thread (reference:
  engine.cc:1106 Indexing loop); implementations keep host-side state
  swaps atomic (build new arrays, then publish by reference assignment).
"""

from __future__ import annotations

import abc
from typing import Any

import numpy as np

from vearch_tpu.engine.raw_vector import RawVectorStore
from vearch_tpu.engine.types import IndexParams, MetricType
from vearch_tpu.tools import lockcheck


class VectorIndex(abc.ABC):
    """Base class for all vector index types."""

    #: whether train() must run before the index can serve (IVF family)
    needs_training: bool = False

    def __init__(self, params: IndexParams, store: RawVectorStore):
        self.params = params
        self.store = store
        self.metric: MetricType = params.metric_type
        self.trained = not self.needs_training
        self.indexed_count = 0  # rows absorbed into the index structure
        # serialises concurrent absorb() from search threads / the
        # background build thread (reference: engine.cc CAS state machine);
        # minted via lockcheck so VEARCH_LOCKCHECK=1 stress runs verify
        # the narrowed search critical sections hold no surprise orders
        self._absorb_lock = lockcheck.make_lock("index_absorb")

    @property
    def input_dim(self) -> int:
        """Wire-format vector length (binary indexes pack 8 bits/byte —
        reference: faiss binary vectors are d/8 uint8)."""
        return self.store.dimension

    def decode_input(self, batch: np.ndarray) -> np.ndarray:
        """Decode wire-format vectors [b, input_dim] into the stored
        representation [b, dimension] (identity for float indexes)."""
        return np.asarray(batch, dtype=np.float32)

    @abc.abstractmethod
    def search(
        self,
        queries: np.ndarray,
        k: int,
        valid_mask: np.ndarray | None,
        params: dict | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Batch search. queries [B, d] f32; valid_mask [n] bool or None.

        `params` carries per-request overrides (nprobe, rerank, ...) —
        the reference's request-level index_params (doc_query.go
        index_params riding each search request).

        Returns (scores [B, k] similarity-oriented (higher=better),
        docids [B, k] int; -1 and -inf pad missing results).
        """

    def train(self, sample: np.ndarray) -> None:
        """Train quantizers on a sample (no-op for non-trained indexes)."""
        self.trained = True

    def absorb(self, upto: int) -> None:
        """Absorb raw-vector rows [indexed_count, upto) into the index
        structure (realtime ingest pump; reference: vector_manager.h:76
        AddRTVecsToIndex). FLAT-style indexes that search the raw store
        directly just advance the counter."""
        self.indexed_count = upto

    def device_footprint_bytes(self) -> int:
        """Modeled resident HBM bytes of this index's device state
        (ops/perf_model.py — the rows-per-chip capacity planner input).
        Default covers indexes that search the raw store directly; index
        types with extra device state (mirrors, bucket tensors) add it."""
        from vearch_tpu.ops import perf_model

        return perf_model.raw_store_footprint_bytes(
            self.store.capacity,
            self.store.dimension,
            self.store.store_dtype.itemsize,
        )

    def device_footprint_per_device_bytes(self) -> int:
        """Modeled resident HBM bytes on EACH chip. Single-device
        indexes hold everything on one chip; mesh-serving indexes
        override with the sharded/replicated split
        (ops/perf_model.per_device_bytes)."""
        return self.device_footprint_bytes()

    def mesh_info(self) -> dict[str, Any] | None:
        """Mesh data-plane placement summary, None when this index is
        not mesh-serving (single device)."""
        return None

    def ivf_info(self) -> dict[str, Any] | None:
        """The published padded bucket table of an IVF index (rows,
        nlist, cap, bytes, fill, publishes, seconds), None for an index
        that publishes none or has not yet."""
        return None

    def refine_info(self) -> dict[str, Any] | None:
        """The three-stage refinement funnel of an index that serves
        one (index/binary.py IVFRABITQ: searches, rows scored a stage,
        depths, device bytes of the three representations), None for
        every other index and before the first such search."""
        return None

    def select_info(self) -> dict[str, Any] | None:
        """Full-scan dispatches by site and by how wide the widest sort
        of the program's selection was (index/ivf.py IVFPQ and what
        derives from it), None for every other index and before the
        first such dispatch."""
        return None

    def tiering_info(self) -> dict[str, Any] | None:
        """Tiered-storage summary (per-tier hit/miss/pin counters,
        residency bytes — see docs/TIERING.md), None when this index
        serves entirely from device memory."""
        return None

    # -- index-health drift gauges (obs/quality.py collect_health) -------

    def cell_populations(self) -> list[int] | None:
        """Per-cell member counts for population-imbalance gauges, None
        for index types without a coarse partitioning (FLAT)."""
        return None

    def reconstruction_error(self, sample: int = 256,
                             seed: int = 0) -> float | None:
        """Mean relative reconstruction error ‖x − dequant(quant(x))‖ /
        ‖x‖ over `sample` STORED rows (the codes actually scored at
        serve time, not a fresh re-encode — so stale codebooks and
        corrupt scales both move the gauge). None when the index stores
        rows exactly or is untrained. Host-side only: implementations
        must not dispatch device programs (this runs on the quality
        monitor's background cadence)."""
        return None

    def close(self) -> None:
        """Release background resources (prefetch workers, mmaps).
        Idempotent; default is a no-op for in-memory indexes."""

    # -- persistence (index-specific state only; raw vectors are dumped by
    #    the engine — reference: index is rebuildable, vectors are durable)

    def dump_state(self) -> dict[str, Any]:
        return {}

    def load_state(self, state: dict[str, Any]) -> None:
        pass
