"""Per-partition engine: orchestrates table + vector stores + indexes +
deletion bitmap.

TPU-native re-design of the reference's gamma Engine (reference:
internal/engine/search/engine.h:35 `vearch::Engine`; search entry
engine.cc:242, upsert engine.cc:691, brute-force fallback engine.cc:280-302,
background build engine.cc:966/1106). One Engine instance per partition;
the cluster layer (ps) holds a registry of them.

Write model (TPU-first): everything is append-only. An update soft-deletes
the old docid and appends a new row, so device vector buffers never mutate
rows — deletions are masked inside the top-k kernel. Compaction is an
offline rebuild (rebuild_index), as in the reference.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
import uuid
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from vearch_tpu.cluster import metrics as cluster_metrics
from vearch_tpu.engine.bitmap import BitmapManager
from vearch_tpu.obs import accounting as _acct
from vearch_tpu.engine.raw_vector import RawVectorStore
from vearch_tpu.engine.table import Table
from vearch_tpu.engine.types import (
    DataType,
    IndexParams,
    IndexStatus,
    ScalarIndexType,
    SearchResult,
    SearchResultItem,
    TableSchema,
)
from vearch_tpu.index.base import VectorIndex
from vearch_tpu.index.registry import create_index
from vearch_tpu.utils import log

_log = log.get("engine")


@dataclass
class SearchRequest:
    """One batched vector search (reference: api_data/request.h:18).

    vectors: field name -> [B, d] query matrix. Multiple fields are merged
    with `field_weights` (reference: WeightedRanker, doc_query.go:202).
    filters: a scalar-filter AST (vearch_tpu.scalar.filter) or None.
    """

    vectors: dict[str, np.ndarray]
    k: int = 10
    filters: Any = None
    include_fields: list[str] | None = None
    brute_force: bool = False  # force exact scan even when indexed
    field_weights: dict[str, float] = field(default_factory=dict)
    index_params: dict[str, Any] = field(default_factory=dict)  # nprobe etc.
    # {field: (min_score, max_score)} — per-field windows on each
    # field's OWN metric-oriented score, applied inside the rank merge
    # for multi-field requests and on the final score for single-field
    # ones (reference: min_score/max_score per vector query,
    # test_document_search.py test_..._with_score_filter)
    score_bounds: dict[str, tuple] | None = None
    # normalized scalar-field sort specs (engine/sort.py parse_sort):
    # hits get per-spec sort values attached and each query's items are
    # returned ordered by them (reference: SortFields on the request,
    # doc_query.go:1543; sortorder value compare)
    sort: list[dict] | None = None
    # fields-free fast path: return ColumnarSearchResults (flat keys,
    # hits per query, one flat score buffer) instead of per-item objects
    # — the serving shape of the columnar wire; skips the microbatcher
    raw_results: bool = False
    # when not None, the engine records per-phase wall times into it
    # (reference: per-request trace:true timing breakdown,
    # client/client.go:521-565 + PerfTool, index_model.h:24)
    trace: dict[str, float] | None = None
    # cooperative cancellation (reference: RequestContext kill status,
    # api_data/request_context.h + Set/DeleteKillStatus c_api): checked
    # at phase boundaries — a killed request aborts before its next
    # device dispatch rather than mid-kernel
    ctx: "RequestContext | None" = None


class RequestKilled(Exception):
    pass


class RequestContext:
    """Kill flag for one in-flight request (reference:
    api_data/request_context.h; the PS slow-request killer and the
    /ps/kill admin both flip it).

    `deadline` (absolute `time.monotonic()` seconds — NOT wall epoch:
    an NTP step must not expire or immortalize a live request) arms
    check() itself: a request past its deadline self-kills at the next
    phase boundary — between device dispatches, never mid-kernel —
    without waiting on the PS killer loop's tick. `reason_code` is the
    bounded label the PS exports on vearch_requests_killed_total."""

    def __init__(self, request_id: str = "",
                 deadline: float | None = None):
        self.request_id = request_id
        self.deadline = deadline
        self.killed = False
        self.reason = ""
        self.reason_code = ""

    def kill(self, reason: str = "killed", code: str = "operator") -> None:
        self.killed = True
        self.reason = reason
        self.reason_code = code

    def check(self) -> None:
        if (not self.killed and self.deadline is not None
                and time.monotonic() > self.deadline):
            self.kill("deadline exceeded", code="deadline")
        if self.killed:
            raise RequestKilled(self.reason or "request killed")


class _FieldBuild:
    """In-flight scalar field-index build: target type, completion
    event, and the build's error (read by sync joiners)."""

    __slots__ = ("value", "done", "error")

    def __init__(self, value: str):
        self.value = value
        self.done = threading.Event()
        self.error: BaseException | None = None


class Engine:
    def __init__(self, schema: TableSchema, data_dir: str | None = None):
        self.schema = schema
        self.data_dir = data_dir
        self.table = Table(schema)
        self.bitmap = BitmapManager()
        self.vector_stores: dict[str, RawVectorStore] = {}
        self.indexes: dict[str, VectorIndex] = {}
        self.status = IndexStatus.UNINDEXED
        self.last_build_error: BaseException | None = None
        # current/last index-build job record (build_index fills it) —
        # the PS serves these at GET /ps/jobs and rides the terminal
        # status on heartbeats for the master's /cluster/health rollup
        self.build_job: dict | None = None
        # optional terminal-state sink (PS wires build-duration
        # histograms through it; covers background auto-builds too)
        self.build_observer = None
        # optional staleness sink for the search-quality layer (lint
        # VL105): fired on every wholesale index replacement — retrain
        # rebuilds the compressed serving tiers (int8 mirror AND the
        # stage-0 bit planes) in place, so queued shadow samples must
        # not be scored against the pre-rebuild snapshot. The PS resets
        # its QualityMonitor through build_observer; embedded users
        # (bench, SDK-local engines) wire this directly.
        self.mutation_observer = None
        self._write_lock = threading.Lock()
        # monotone data version: bumped under _write_lock by every
        # mutation that can change search results (upsert, delete,
        # schema/scalar-index changes). The serving caches key on it
        # for exact invalidation — stale entries are unreachable the
        # instant a write lands, and simply age out of their LRUs.
        self.data_version = 0
        # scalar-filter bitmap cache: (filter-json, data_version, n) ->
        # combined alive∧filter mask, so repeated filtered searches
        # skip both bitmap reconstruction and the columnar filter scan.
        # Cached masks are served without a copy — callers treat
        # `valid` as read-only (they already do).
        self._filter_cache: OrderedDict[tuple, np.ndarray] = OrderedDict()
        self._filter_cache_lock = threading.Lock()
        self._filter_cache_max = 128
        self.filter_cache_hits = 0
        self.filter_cache_misses = 0
        # field -> in-flight build marker; stops the heartbeat reconcile
        # loop re-spawning a build every 2s while a long background build
        # has yet to publish (flags only flip at publish time), lets sync
        # callers join an identical in-flight build, and gates publish on
        # the marker still being current (supersede/remove cancels it)
        self._field_builds: dict[str, _FieldBuild] = {}
        # continuous batching (engine/batching.py): lazily started on
        # the first qualifying search so idle engines spawn no thread
        self.micro_batch = True
        self.micro_batch_max_rows = 1024
        # age bound on a partially-filled shape bucket (ms); 0 = dispatch
        # the moment the dispatcher is free (zero added idle latency)
        self.batch_delay_ms = 0.0
        self._microbatcher = None
        # padded shape buckets (ops/perf_model.ROW_BUCKETS /
        # FETCH_K_TIERS): every serving dispatch is quantized to the
        # declared grid so the warmed program set is finite and
        # mixed-k traffic co-batches. Off reverts to free-form shapes
        # (the pre-bucket baseline, kept for A/B).
        self.shape_buckets = True
        # padding-waste accounting (best-effort counters; the doctor
        # flags sustained waste, /ps/stats surfaces them)
        self.pad_real_rows = 0
        self.pad_padded_rows = 0
        self.pad_waste_bytes = 0
        self._scalar_manager = None
        if schema.composite_indexes or any(
            f.scalar_index.value != "NONE" for f in schema.scalar_fields()
        ):
            from vearch_tpu.scalar.manager import ScalarIndexManager

            self._scalar_manager = ScalarIndexManager(schema)

        for f in schema.vector_fields():
            params = f.index or IndexParams()
            dtype = params.get("store_dtype", "float32")
            store_type = str(params.get("store_type", "MemoryOnly"))
            disk_index = params.index_type.upper() in (
                "DISKANN", "DISKANN_STATIC"
            )
            if store_type in ("Disk", "RocksDB") or disk_index:
                # disk tier (reference: RocksDBRawVector + DiskANN static
                # raw data): rows live in an mmap, not host RAM
                from vearch_tpu.engine.disk_vector import DiskRawVectorStore

                base = data_dir or tempfile.mkdtemp(prefix="vearch_disk_")
                store: RawVectorStore = DiskRawVectorStore(
                    f.dimension,
                    directory=os.path.join(base, f"disk_{f.name}"),
                    store_dtype=dtype,
                    row_cache_mb=int(params.get("row_cache_mb", 64)),
                )
            else:
                store = RawVectorStore(f.dimension, store_dtype=dtype)
            self.vector_stores[f.name] = store
            self.indexes[f.name] = create_index(params, store)

    # -- writes --------------------------------------------------------------

    def upsert(self, docs: list[dict[str, Any]]) -> list[str]:
        """Add-or-update a batch; returns assigned doc keys.

        Mirrors reference engine.cc:691 AddOrUpdate: existing key ==
        update -> old docid soft-deleted, new row appended everywhere.
        Partial updates carry omitted fields forward from the replaced
        row — an upsert without the vector updates scalars only
        (reference: test_document_upsert.py update() add(has_vector=
        False)); a NEW document must bring every vector field."""
        vf = self.schema.vector_fields()
        keys: list[str] = []
        with self._write_lock:
            # batch the vector appends: one host copy per field per call;
            # decode wire format (e.g. packed binary) via the index hook.
            # A doc whose vector is absent OR null inherits the row it
            # replaces — the latest provider of the same _id earlier in
            # THIS batch, else the stored row. All resolution and
            # validation happens BEFORE any mutation (a bad batch fails
            # whole; a mid-batch failure would desync the docid==row-id
            # invariant between table and vector stores forever), and
            # deterministically from engine state so raft replicas
            # resolve identically.
            for doc in docs:
                self.table.validate(
                    {k: v for k, v in doc.items() if k != "_id"}
                )
            mats = {}
            for f in vf:
                idx = self.indexes[f.name]
                store = self.vector_stores[f.name]
                have = [i for i, d in enumerate(docs)
                        if d.get(f.name) is not None]
                if len(have) == len(docs):
                    raw = np.asarray([d[f.name] for d in docs]).reshape(
                        len(docs), idx.input_dim
                    )
                    mats[f.name] = idx.decode_input(raw)
                    continue
                out = np.zeros((len(docs), store.dimension), np.float32)
                if have:
                    raw = np.asarray(
                        [docs[i][f.name] for i in have]
                    ).reshape(len(have), idx.input_dim)
                    out[have] = idx.decode_input(raw)
                latest: dict[str, int] = {}  # key -> out row in this batch
                for i, d in enumerate(docs):
                    key = str(d["_id"]) if "_id" in d else None
                    if d.get(f.name) is not None:
                        if key is not None:
                            latest[key] = i
                        continue
                    src = latest.get(key) if key is not None else None
                    if src is not None:
                        out[i] = out[src]
                        latest[key] = i
                        continue
                    old = (self.table.docid_of(key)
                           if key is not None else None)
                    if old is None:
                        raise ValueError(
                            f"document {key!r} omits vector field "
                            f"{f.name!r} and has no existing row to "
                            f"inherit it from"
                        )
                    out[i] = np.asarray(store.get(old), dtype=np.float32)
                    latest[key] = i
                mats[f.name] = out
            merged_docs = []
            for i, doc in enumerate(docs):
                key = str(doc["_id"]) if "_id" in doc else uuid.uuid4().hex
                fields = {k: v for k, v in doc.items() if k != "_id"}
                prev_id = self.table.docid_of(key)
                if prev_id is not None:
                    # partial scalar update: omitted fields keep their
                    # previous values — but only fields the previous doc
                    # actually SET (fixed columns materialize 0-defaults;
                    # carrying those forward would index phantom values)
                    prev_set = self.table.set_fields_of(prev_id)
                    for name, val in self.table.get_fields(
                            prev_id, list(prev_set)).items():
                        fields.setdefault(name, val)
                docid, old = self.table.add(key, fields)
                if old is not None:
                    self.bitmap.set_deleted(old)
                keys.append(key)
                merged_docs.append(fields)
            for f in vf:
                self.vector_stores[f.name].add(mats[f.name])
            if self._scalar_manager is not None:
                self._scalar_manager.add_docs(
                    merged_docs, len(self.table._keys) - len(docs)
                )
            self.data_version += 1
        self._maybe_start_build()
        return keys

    def delete(self, keys: list[str]) -> int:
        n = 0
        with self._write_lock:
            for key in keys:
                docid = self.table.delete(key)
                if docid is not None:
                    self.bitmap.set_deleted(docid)
                    n += 1
            if n:
                self.data_version += 1
        return n

    def get(
        self,
        keys: list[str],
        fields: list[str] | None = None,
        vector_value: bool = False,
    ) -> list[dict]:
        """Fetch docs by key. Vector payloads ride only when
        `vector_value` is set or a vector field is named in `fields`
        (reference: the `vector_value` request flag)."""
        out = []
        for key in keys:
            docid = self.table.docid_of(key)
            if docid is None or self.bitmap.is_deleted(docid):
                continue
            doc = {"_id": key, **self.table.get_fields(docid, fields)}
            for name, store in self.vector_stores.items():
                if vector_value or (fields is not None and name in fields):
                    doc[name] = store.get(docid).tolist()
            out.append(doc)
        return out

    @property
    def doc_count(self) -> int:
        """Alive docs (reference: engine status doc_num minus deletes)."""
        return self.table.doc_count - self.bitmap.deleted_count

    def memory_usage_bytes(self) -> int:
        """Host-side memory of the durable structures (raw vectors +
        quantized mirrors + codes). Drives the resource-limit write guard
        (reference: store_writer.go:82-95 resource check every 50k docs;
        memory/memoryManager.cc accounting)."""
        total = 0
        for store in self.vector_stores.values():
            if getattr(store, "durable_on_disk", False):
                total += store.memory_usage_bytes()  # page cache, not RSS
            else:
                total += store.host_view().nbytes  # used rows, not capacity
        for index in self.indexes.values():
            mirror = getattr(index, "_mirror", None)
            if mirror is not None:
                n = mirror.count
                total += n * (mirror.dimension + 8)  # int8 row + scale + vsq
            codes = getattr(index, "_codes", None)
            if codes is not None:
                total += codes.nbytes
        return total

    def quality_info(self) -> dict[str, Any]:
        """Index-health raw numbers for the quality monitor's drift
        gauges (obs/quality.py collect_health): deleted/unindexed
        fractions plus per-field quantization reconstruction error and
        cell-population imbalance. Host work only — no device dispatch
        (the monitor samples this on a background cadence)."""
        total = int(self.table.doc_count)
        deleted = int(self.bitmap.deleted_count)
        info: dict[str, Any] = {
            "doc_count": total - deleted,
            "deleted_count": deleted,
            "deleted_frac": deleted / total if total else 0.0,
            "data_version": int(self.data_version),
            "fields": {},
        }
        for name, index in self.indexes.items():
            n = int(index.store.count)
            if index.needs_training and n:
                unindexed = (n - min(int(index.indexed_count), n)) / n
            else:
                # FLAT-family indexes scan the raw store directly: the
                # tail is always searched, never "unindexed"
                unindexed = 0.0
            f: dict[str, Any] = {
                "index_type": index.params.index_type,
                "trained": bool(index.trained),
                "indexed_count": int(index.indexed_count),
                "unindexed_frac": unindexed,
            }
            try:
                f["recon_error"] = index.reconstruction_error()
            except Exception as e:
                cluster_metrics.internal_error("engine.quality_info", e)
                f["recon_error"] = None
            pops = index.cell_populations()
            if pops:
                arr = np.asarray(pops, dtype=np.float64)
                mean = float(arr.mean())
                f["ncells"] = len(pops)
                f["cell_min"] = int(arr.min())
                f["cell_max"] = int(arr.max())
                f["cell_imbalance_cv"] = (
                    float(arr.std() / mean) if mean > 0 else 0.0
                )
            info["fields"][name] = f
        return info

    def query(
        self,
        filters: Any = None,
        limit: int = 50,
        offset: int = 0,
        include_fields: list[str] | None = None,
        vector_value: bool = False,
        order_by_key: bool = True,
        sort: list[dict] | None = None,
    ) -> list[dict]:
        """Scalar-only query: filter docs without vector search
        (reference: engine.cc:404 ScalarIndexQuery-only path +
        /document/query). Vector payload rules match get().

        Matches are returned in _id order by default so the router's
        merge-then-slice global pagination is correct regardless of
        insertion order; pass order_by_key=False for drain-style callers
        (delete-by-filter) that don't care and shouldn't pay the sort.
        With `sort` (normalized specs, engine/sort.py), matches order by
        the scalar sort keys instead — _id tie-break — and each returned
        doc carries "_sort" values for the router's cross-partition
        merge (reference: QueryFieldSortExecute, client.go:1062).
        """
        n = self.table.doc_count
        valid = self.bitmap.valid_mask(n)
        if filters is not None:
            from vearch_tpu.scalar.filter import evaluate_filter

            valid = valid & evaluate_filter(filters, self, n)
        matched = np.nonzero(valid)[0]
        sort_rows: list[list] | None = None
        if sort and matched.size:
            matched, sort_rows = self._sorted_matches(matched, sort)
        elif order_by_key and matched.size:
            keys = np.array(
                [self.table.key_of(int(i)) for i in matched], dtype=object
            )
            matched = matched[np.argsort(keys, kind="stable")]
        hits = matched[offset : offset + limit]
        out = []
        for pos, docid in enumerate(hits):
            docid = int(docid)
            doc = {"_id": self.table.key_of(docid)}
            doc.update(self.table.get_fields(docid, include_fields))
            for name, store in self.vector_stores.items():
                if vector_value or (
                    include_fields is not None and name in include_fields
                ):
                    doc[name] = store.get(docid).tolist()
            if sort_rows is not None:
                doc["_sort"] = sort_rows[offset + pos]
            out.append(doc)
        return out

    def _sorted_matches(
        self, matched: np.ndarray, specs: list[dict]
    ) -> tuple[np.ndarray, list[list]]:
        """Order matched docids by the sort specs (stable, _id
        tie-break). Returns (ordered docids, per-docid sort values in
        the SAME order). Fixed numeric columns ride a vectorised
        np.lexsort; string/missing-capable fields fall back to a cmp
        sort."""
        from vearch_tpu.engine.sort import ID_FIELD, SCORE_FIELD, row_sort_key

        ids = matched.tolist()
        keys = [self.table.key_of(int(i)) for i in ids]
        value_cols: list[list] = []
        all_fixed = True
        for s in specs:
            f = s["field"]
            if f == ID_FIELD:
                value_cols.append(keys)
                all_fixed = False
                continue
            if f == SCORE_FIELD:
                # no vector score in a scalar query; router rejects this
                # upstream, a direct caller gets None values (sort last)
                value_cols.append([None] * len(ids))
                all_fixed = False
                continue
            try:
                col = self.table.column(f)
                value_cols.append(col[matched].tolist())
            except KeyError:
                # string (or unknown) field: per-doc lookup, None when
                # the doc lacks it
                all_fixed = False
                try:
                    scol = self.table.string_column(f)
                    value_cols.append([scol[i] for i in ids])
                except KeyError:
                    value_cols.append([None] * len(ids))
        if all_fixed and value_cols:
            # numeric fast path: lexsort with least-significant key
            # first -> feed (tie-break key, reversed spec columns)
            import numpy as _np

            # least-significant key first: (_id tie-break, then spec
            # columns in reverse). Keys are str -> unicode dtype
            # (np.lexsort rejects object arrays).
            lex_keys = [_np.asarray(keys)]
            for s, col in zip(reversed(specs), reversed(value_cols)):
                arr = _np.asarray(col)
                if arr.dtype == bool or arr.dtype.kind == "u":
                    arr = arr.astype(_np.int64)  # negate-safe
                lex_keys.append(-arr if s["desc"] else arr)
            order = _np.lexsort(lex_keys)
        else:
            rows = list(range(len(ids)))
            rows.sort(key=row_sort_key(
                specs,
                lambda r: [value_cols[c][r] for c in range(len(specs))],
                tie_key=lambda r: keys[r],
            ))
            order = rows
        ordered = matched[np.asarray(order, dtype=np.int64)]
        sort_rows = [
            [value_cols[c][r] for c in range(len(specs))] for r in order
        ]
        return ordered, sort_rows

    # -- index lifecycle -----------------------------------------------------

    def _maybe_start_build(self) -> None:
        """Kick off a background train+absorb once the training threshold is
        crossed (reference: the Indexing thread trains when doc volume
        passes training_threshold, engine.cc:1106). CAS-style guard mirrors
        the reference's IDLE->STARTING state machine (engine.cc:967).
        An engine whose indexes train nothing (FLAT, HNSW) is built, i.e.
        absorbed and marked INDEXED, at the same threshold."""
        trains = any(idx.needs_training for idx in self.indexes.values())
        needs = [
            (name, idx)
            for name, idx in self.indexes.items()
            if (not idx.trained or not trains)
            and self.vector_stores[name].count >= self._training_threshold(idx)
        ]
        if not needs or self.status != IndexStatus.UNINDEXED:
            return
        self.status = IndexStatus.TRAINING
        t = threading.Thread(target=self.build_index, daemon=True,
                             name="engine-build")
        t.start()
        self._build_thread = t

    def wait_for_index(self, timeout: float | None = None) -> None:
        """Join an in-flight background build (tests / explicit flush)."""
        t = getattr(self, "_build_thread", None)
        if t is not None:
            t.join(timeout)

    def start_refresh_loop(self) -> None:
        """Background realtime pump: absorb new rows into every trained
        index at refresh_interval cadence so searches never pay the
        absorb cost inline (reference: engine.cc:1106-1158 Indexing loop
        sleeping refresh_interval_ between AddRTVecsToIndex passes)."""
        with self._write_lock:  # ordered against close()'s _closed write
            if getattr(self, "_refresh_thread", None) is not None:
                return
            if (getattr(self, "_closed", None) is not None
                    and self._closed.is_set()):
                return  # closed engines stay closed
            self._closed = threading.Event()

        def loop():
            while not self._closed.wait(
                max(self.schema.refresh_interval_ms, 50) / 1e3
            ):
                for name, index in self.indexes.items():
                    if index.trained:
                        try:
                            index.absorb(self.vector_stores[name].count)
                        except Exception as e:
                            self.last_build_error = e

        self._refresh_thread = threading.Thread(target=loop, daemon=True,
                                                name="engine-refresh")
        self._refresh_thread.start()

    def close(self) -> None:
        # under _write_lock, mirroring the lazy creation in search() and
        # the _closed creation in start_refresh_loop(): otherwise a
        # concurrent search could construct a fresh batcher after this
        # stop (or a racing start_refresh_loop could clobber the set
        # event with a fresh one), leaking threads bound to a closed
        # engine
        with self._write_lock:
            if getattr(self, "_closed", None) is None:
                # no refresh loop ever started; still record closedness
                # so apply_config can't re-enable micro-batching later
                self._closed = threading.Event()
            self._closed.set()
            self.micro_batch = False
            if self._microbatcher is not None:
                self._microbatcher.stop()
                self._microbatcher = None
        # outside _write_lock: index close only stops background tier
        # workers (prefetchers) and must not order under the write path
        for index in self.indexes.values():
            try:
                index.close()
            except Exception as e:
                log.warn("index close failed: %s", e)

    def apply_config(self, cfg: dict[str, Any]) -> dict[str, Any]:
        """Runtime-mutable engine config (reference: master /config API ->
        etcd -> PS watch, cluster_api.go:294-307; engine cache / limits).
        Supported: refresh_interval_ms, training_threshold, plus default
        index params merged per vector field."""
        if "refresh_interval_ms" in cfg:
            self.schema.refresh_interval_ms = int(cfg["refresh_interval_ms"])
        if "training_threshold" in cfg:
            self.schema.training_threshold = int(cfg["training_threshold"])
        if "micro_batch" in cfg:
            # under _write_lock to order against close(): an unlocked
            # check could pass just before close() completes and then
            # re-enable batching on the closed engine — search() would
            # lazily spawn a dispatcher thread bound to a dead engine
            with self._write_lock:
                closed = getattr(self, "_closed", None)
                if closed is None or not closed.is_set():
                    self.micro_batch = bool(cfg["micro_batch"])
        if "micro_batch_max_rows" in cfg:
            self.micro_batch_max_rows = int(cfg["micro_batch_max_rows"])
            mb = self._microbatcher
            if mb is not None:  # propagate to a live batcher
                mb.max_rows = self.micro_batch_max_rows
        if "batch_delay_ms" in cfg:
            self.batch_delay_ms = float(cfg["batch_delay_ms"])
            mb = self._microbatcher
            if mb is not None:  # propagate to a live scheduler
                mb.max_delay_ms = self.batch_delay_ms
        if "shape_buckets" in cfg:
            # A/B escape hatch: free-form dispatch shapes (the
            # pre-bucket baseline). The scheduler reads this per submit,
            # so flipping it also reverts co-batching to exact-k keys.
            self.shape_buckets = bool(cfg["shape_buckets"])
        if "mesh_shape" in cfg:
            # serving-mesh shape ("DxQ", [data, query], or device
            # count): fans into every vector field's index params, same
            # pattern as mesh_serving; parallel/mesh.mesh_from_shape
            # resolves it to one cached Mesh so the program caches and
            # the sharded row caches key consistently
            for index in self.indexes.values():
                index.params.params["mesh_shape"] = cfg["mesh_shape"]
        if "mesh_serving" in cfg:
            # space-level toggle for the multi-chip data plane: fan the
            # mode into every vector field's index params (per-field
            # overrides still win via index_params below)
            for index in self.indexes.values():
                index.params.params["mesh_serving"] = cfg["mesh_serving"]
        for name, params in (cfg.get("index_params") or {}).items():
            if name in self.indexes:
                self.indexes[name].params.params.update(params)
        if cfg.get("warmup"):
            # re-trace after changing warmup_batches / index params at
            # runtime without waiting for the next build
            self.warmup()
        return {
            "refresh_interval_ms": self.schema.refresh_interval_ms,
            "training_threshold": self.schema.training_threshold,
        }

    # -- online scalar field indexes (reference: AddFieldIndexWithParams /
    #    RemoveFieldIndex, c_api/gamma_api.h:166,181; Go seam
    #    gammacb/gamma.go:538,591 — dedicated add-field/remove-field
    #    threads build while searches keep serving) -------------------------

    def add_field_index(
        self, field: str, index_type: str = "INVERTED",
        background: bool = True,
    ) -> None:
        """Build a scalar index on a live field. The build runs over a
        snapshot of the column WITHOUT the write lock (searches keep
        scanning meanwhile), then catches up and publishes atomically
        under the lock — from that moment filters use the index."""
        f = self.schema.field(field)
        if f.data_type is DataType.VECTOR:
            raise ValueError(f"{field} is a vector field")
        itype = ScalarIndexType(index_type.upper())
        if itype is ScalarIndexType.NONE:
            return self.remove_field_index(field)
        with self._write_lock:
            cur = self._field_builds.get(field)
            if cur is not None and cur.value == itype.value:
                if not background:
                    # sync contract: the index must be live on return,
                    # even when an identical build is already in flight
                    pending = cur
                else:
                    return  # identical background build already in flight
            else:
                pending = None
                marker = _FieldBuild(itype.value)
                self._field_builds[field] = marker
        if pending is not None:
            pending.done.wait()
            if pending.error is not None:
                # joining must not report success for a failed build
                raise pending.error
            return

        def build() -> None:
            from vearch_tpu.scalar.manager import _NUMERIC
            from vearch_tpu.scalar.indexes import (
                BitmapScalarIndex, InvertedScalarIndex,
            )

            if itype is ScalarIndexType.BITMAP:
                index = BitmapScalarIndex()
            else:
                dtype = _NUMERIC.get(f.data_type)
                index = InvertedScalarIndex(
                    np.dtype(dtype) if dtype else np.dtype(object)
                )

            def rows(lo: int, hi: int):
                try:
                    return self.table.column(field)[lo:hi]
                except KeyError:
                    return self.table.string_column(field)[lo:hi]

            def indexable(docid: int, value) -> bool:
                # presence-gated like every other index-build path:
                # fixed-column 0-defaults of never-set fields must not
                # become filterable values
                return (value is not None
                        and field in self.table.set_fields_of(docid))

            built = 0
            # bulk phase, lock-free: columns are append-only so the
            # captured slice is stable
            while True:
                hi = self.table.doc_count
                if hi <= built:
                    break
                for docid, value in enumerate(rows(built, hi), start=built):
                    if indexable(docid, value):
                        index.add(value, docid)
                built = hi
            with self._write_lock:
                if self._field_builds.get(field) is not marker:
                    # superseded mid-build (a remove, or a build of a
                    # different type): publishing now would resurrect a
                    # dropped index or clobber the newer build
                    return
                # exact catch-up: rows that landed since the last pass
                hi = self.table.doc_count
                for docid, value in enumerate(rows(built, hi), start=built):
                    if indexable(docid, value):
                        index.add(value, docid)
                if self._scalar_manager is None:
                    from vearch_tpu.scalar.manager import ScalarIndexManager

                    self._scalar_manager = ScalarIndexManager(self.schema)
                self._scalar_manager.add_field(field, index)
                f.scalar_index = itype  # dumps persist the new schema
                self.data_version += 1

        def run() -> None:
            try:
                build()
            except BaseException as e:
                marker.error = e
                if not background:
                    raise
                _log.warning("background field-index build %r failed: %s",
                             field, e)
            finally:
                with self._write_lock:
                    # pop only OUR marker: an overlapping build of a
                    # different type replaced it, and erasing that one
                    # would let the heartbeat reconcile spawn duplicates
                    if self._field_builds.get(field) is marker:
                        self._field_builds.pop(field)
                marker.done.set()

        if background:
            t = threading.Thread(
                target=run, daemon=True,
                name=f"vearch-field-index-{field}",
            )
            t.start()
        else:
            run()

    def add_schema_field(self, f) -> None:
        """Online schema evolution: add a NEW scalar field (reference:
        updateSpaceFields — only additions allowed on live spaces).
        Idempotent; vector fields are rejected."""
        if f.data_type is DataType.VECTOR:
            raise ValueError("vector fields cannot be added to a live space")
        target = f.scalar_index
        with self._write_lock:
            if any(x.name == f.name for x in self.schema.fields):
                return
            # append with NO index flag: the flag flips only when the
            # build publishes — the invariant the heartbeat reconcile
            # relies on to retry a failed build (flag != master's
            # expectation) instead of believing a dead index is live
            f.scalar_index = ScalarIndexType.NONE
            self.schema.fields.append(f)
            self.table.add_field(f)
            self.data_version += 1
        if target is not ScalarIndexType.NONE:
            self.add_field_index(f.name, target.value)

    def remove_field_index(self, field: str) -> None:
        """Drop a field's scalar index; in-flight filtered searches fall
        back to the columnar scan (filter.py tolerates the race)."""
        f = self.schema.field(field)
        with self._write_lock:
            # cancel any in-flight build: orphaning its marker makes the
            # publish-currency check refuse, so the dropped index cannot
            # resurrect after this remove
            self._field_builds.pop(field, None)
            if self._scalar_manager is not None:
                self._scalar_manager.remove_field(field)
            f.scalar_index = ScalarIndexType.NONE
            self.data_version += 1

    def build_index(self, field_name: str | None = None,
                    op: str = "build") -> None:
        """Train + absorb all current rows (reference: engine.cc:966
        BuildIndex -> Indexing thread; here synchronous — the cluster
        layer wraps it in a background thread).

        The build is an observable job: `self.build_job` tracks phase
        (train / assign / publish / warmup), progress (docs_done /
        docs_total) and terminal status while the build runs, with the
        real wall window of each phase kept as `_phase_spans` rows for
        the PS to replay into /debug/traces."""
        t_start = time.monotonic()
        # one wall anchor for span epochs + operator-facing timestamps;
        # phase durations are measured monotonically and offset from it
        wall0 = time.time() - t_start  # lint: allow[wall-clock] span epoch anchor, correlates with collector time
        targets = [
            (name, idx) for name, idx in self.indexes.items()
            if field_name is None or name == field_name
        ]
        job: dict[str, Any] = {
            "op": op, "status": "running", "phase": "train",
            "docs_total": sum(
                self.vector_stores[n].count for n, _ in targets),
            "docs_done": 0,
            "started": wall0 + t_start, "updated": wall0 + t_start,
            "phases_ms": {}, "error": None, "_phase_spans": [],
        }
        self.build_job = job
        phases = job["_phase_spans"]

        def mark(phase: str, t0: float, t1: float) -> None:
            phases.append((f"build.{phase}", int((wall0 + t0) * 1e6),
                           int((t1 - t0) * 1e6)))
            job["phases_ms"][phase] = round(
                job["phases_ms"].get(phase, 0.0) + (t1 - t0) * 1e3, 3)
            job["phase"] = phase
            job["updated"] = wall0 + t1

        # train/assign specialise kernels by design — expected compiles,
        # not serving-path regressions the flight recorder should ring
        from vearch_tpu.obs.flight_recorder import RECORDER

        self.status = IndexStatus.TRAINING
        try:
            with RECORDER.warmup():
                for name, index in targets:
                    store = self.vector_stores[name]
                    if index.needs_training and not index.trained:
                        t0 = time.monotonic()
                        index.train(store.host_view())
                        mark("train", t0, time.monotonic())
                        # which mesh trained the coarse quantizer (None
                        # = single device); the PS replays it as a tag
                        # on the build.train span
                        tm = getattr(index, "last_train_mesh", None)
                        if tm:
                            job["train_mesh"] = tm
                    t0 = time.monotonic()
                    index.absorb(store.count)
                    mark("assign", t0, time.monotonic())
                    job["docs_done"] += store.count
        except Exception as e:
            # a failed (possibly background) build must not wedge the
            # engine in TRAINING: record, reset, keep serving brute-force
            self.last_build_error = e
            self.status = IndexStatus.UNINDEXED
            now = time.monotonic()
            job.update(status="error",
                       error=f"{type(e).__name__}: {e}",
                       duration_seconds=round(now - t_start, 3),
                       updated=wall0 + now)
            self._notify_build(job)
            raise
        t0 = time.monotonic()
        self.status = IndexStatus.INDEXED
        mark("publish", t0, time.monotonic())
        # pre-trace the serving programs for the configured batch buckets
        # now, at publish time, so the first real query never pays the
        # compile stall (no-op unless "warmup_batches" is configured)
        t0 = time.monotonic()
        self.warmup(field_name=field_name)
        mark("warmup", t0, time.monotonic())
        now = time.monotonic()
        job.update(status="done", phase="done",
                   duration_seconds=round(now - t_start, 3),
                   updated=wall0 + now)
        self._notify_build(job)

    def _notify_build(self, job: dict) -> None:
        obs = self.build_observer
        if obs is not None:
            try:
                obs(job)
            except Exception:
                pass  # observability must never fail a build

    def warmup(
        self,
        batches: list[int] | None = None,
        k: int = 10,
        field_name: str | None = None,
    ) -> dict[str, list[int]]:
        """Pre-trace + compile the jitted search programs for the given
        query-batch sizes (default: each index's "warmup_batches" param).

        Runs real searches through the serving path with stored rows as
        queries, so the exact (shape, static-args) specialisations the
        first requests would compile are already in the jit cache — and,
        when the persistent compilation cache is enabled, on disk. The
        perf gates assert the effect: after warmup, repeated same-shape
        searches add ZERO new compiled programs. Returns the batch sizes
        traced per field.
        """
        # warmup compiles are the point, not a serving regression: keep
        # them out of the compile-audit flight recorder's ring
        from vearch_tpu.obs.flight_recorder import RECORDER

        done: dict[str, list[int]] = {}
        with RECORDER.warmup():
            return self._warmup_inner(done, batches, k, field_name)

    def _warmup_inner(self, done, batches, k, field_name):
        for name, index in self.indexes.items():
            if field_name is not None and name != field_name:
                continue
            store = self.vector_stores[name]
            if store.count == 0:
                continue
            b_list = batches if batches is not None else list(
                index.params.get("warmup_batches", []) or []
            )
            if not b_list:
                continue
            # a live row, not zeros: cosine normalisation of an all-zero
            # query would exercise a degenerate code path
            row = np.asarray(store.host_view()[:1], dtype=np.float32)
            valid = self._device_alive_mask(self.table.doc_count)
            kk = max(1, min(int(k), store.count))
            b_set = {int(x) for x in b_list if int(x) > 0}
            if self.shape_buckets:
                # warm the shapes serving will actually dispatch: the
                # engine quantizes rows and fetch-k to the declared
                # buckets, so warming the raw sizes would compile
                # programs no request ever runs
                from vearch_tpu.ops import perf_model as _perf

                kk = _perf.bucket_fetch_k(kk)
                b_set = {_perf.bucket_rows(b) for b in b_set}
            for b in sorted(b_set):
                q = np.repeat(row, b, axis=0)
                if index.trained:
                    index.search(q, kk, valid)
                else:
                    from vearch_tpu.index.flat import FlatIndex

                    FlatIndex(
                        IndexParams(metric_type=index.metric), store
                    ).search(q, kk, valid)
                done.setdefault(name, []).append(b)
        return done

    def note_index_mutation(self, op: str = "") -> None:
        """Staleness hook (lint VL105): forward a wholesale index
        replacement to the wired quality observer. Safe at any
        frequency; observability must never fail the mutation."""
        obs = self.mutation_observer
        if obs is not None:
            try:
                obs(op)
            except Exception:
                pass

    def rebuild_index(self) -> None:
        """Retrain from scratch (reference: engine.cc:1007 RebuildIndex)."""
        for name, index in self.indexes.items():
            params = index.params
            store = self.vector_stores[name]
            self.indexes[name] = create_index(params, store)
        self.status = IndexStatus.UNINDEXED
        self.build_index(op="rebuild")
        # the retrain replaced the quantizers, the int8 mirror AND the
        # stage-0 bit planes wholesale
        self.note_index_mutation(op="rebuild")

    def _training_threshold(self, index: VectorIndex) -> int:
        """Docs required before auto-build starts; explicit build_index()
        ignores it (reference: /index/forcemerge trains immediately)."""
        return int(
            index.params.get(
                "training_threshold", self.schema.training_threshold or 100_000
            )
        )

    # -- search --------------------------------------------------------------

    def _device_alive_mask(self, n: int):
        import jax.numpy as jnp

        key = (self.bitmap.version, n)
        if getattr(self, "_mask_cache_key", None) != key:
            self._mask_cache = jnp.asarray(self.bitmap.valid_mask(n))
            self._mask_cache_key = key
        return self._mask_cache

    def search(self, req: SearchRequest) -> list[SearchResult]:
        """Search entry: compatible concurrent requests pack into padded
        shape buckets and share one device dispatch
        (engine/batching.py); filtered, brute-force, and
        batching-disabled requests run directly."""
        if (
            self.micro_batch
            and req.filters is None
            and not req.brute_force
            and not req.raw_results
            and req.vectors
        ):
            mb = self._microbatcher
            if mb is None:
                with self._write_lock:
                    mb = self._microbatcher
                    # re-check micro_batch under the lock: close() flips
                    # it to False before stopping the batcher
                    if mb is None and self.micro_batch:
                        from vearch_tpu.engine.batching import BatchScheduler

                        mb = self._microbatcher = BatchScheduler(
                            self, max_rows=self.micro_batch_max_rows,
                            max_delay_ms=self.batch_delay_ms,
                        )
            if mb is not None:
                return mb.submit(req)
        # direct path: the whole engine wall slice bills to the bound
        # space (the scheduler path apportions inside _run_bucket)
        t0 = time.monotonic()
        try:
            return self._search_direct(req)
        finally:
            _acct.ACCOUNTANT.charge(
                "device_us", int((time.monotonic() - t0) * 1e6))

    def _filtered_mask(self, filters: Any, n: int) -> np.ndarray:
        """Alive∧filter mask for the first `n` rows, cached on
        (filter expression, data_version, n).

        The version is captured BEFORE evaluation: a write landing
        mid-evaluation bumps data_version, so the (possibly mixed)
        mask stays keyed to the old version and the next search —
        which reads the new version — recomputes. Searches concurrent
        with the write get no weaker ordering than they had uncached.
        """
        from vearch_tpu.scalar.filter import evaluate_filter

        version = self.data_version
        try:
            fkey = json.dumps(filters, sort_keys=True, default=str)
        except (TypeError, ValueError):
            fkey = None  # un-canonicalizable filter object: no caching
        if fkey is not None:
            key = (fkey, version, n)
            with self._filter_cache_lock:
                mask = self._filter_cache.get(key)
                if mask is not None:
                    self._filter_cache.move_to_end(key)
                    self.filter_cache_hits += 1
                    return mask
                self.filter_cache_misses += 1
        mask = self.bitmap.valid_mask(n) & evaluate_filter(
            filters, self, n
        )
        if fkey is not None:
            with self._filter_cache_lock:
                self._filter_cache[key] = mask
                self._filter_cache.move_to_end(key)
                while len(self._filter_cache) > self._filter_cache_max:
                    self._filter_cache.popitem(last=False)
        return mask

    def _search_direct(self, req: SearchRequest) -> list[SearchResult]:
        if not req.vectors:
            raise ValueError("search needs at least one vector field")
        from vearch_tpu.ops import ivf as _ivf_ops

        # Phase profiling (observability tentpole): when req.trace is a
        # dict, every engine phase records its wall window — both as a
        # flat `{phase}_ms` key (the profile=true breakdown) and, with
        # this thread's CPU over it, on the request's dispatch capture
        # (ops/ivf.py phase, beside the index's own windows), whose
        # `_phase_spans` rows the PS turns into retroactive child spans
        # under ps.search. The capture also records which device
        # programs this search launched so the trace can carry measured
        # dispatches next to the perf model's DOCUMENTED_DISPATCHES
        # prediction.
        tracing = req.trace is not None
        capture = _ivf_ops.begin_capture() if tracing else None
        try:
            # the engine's windows abut: each opens where the last
            # closed (ops/ivf.py phase `after`)
            filt = prev = _ivf_ops.phase("engine.filter", request_only=True)
            n = self.table.doc_count
            if req.filters is not None:
                valid = self._filtered_mask(req.filters, n)
            else:
                # no filter -> the alive mask only changes on writes;
                # keep it device-resident so the hot path skips a
                # [n]-bool H2D upload
                valid = self._device_alive_mask(n)
            if tracing:
                req.trace["filter_ms"] = round(
                    (filt.close() - filt.t0) * 1e3, 3)

            metrics = {self.indexes[name].metric for name in req.vectors}
            if len(metrics) > 1:
                raise ValueError(
                    "multi-field search requires a single metric across "
                    f"fields; got {[m.value for m in metrics]}"
                )

            from vearch_tpu.ops import perf_model as _perf

            per_field: dict[str, tuple[np.ndarray, np.ndarray]] = {}
            queries_by_field: dict[str, np.ndarray] = {}
            fetch_k = req.k if len(req.vectors) == 1 else max(req.k * 4, 50)
            if self.shape_buckets:
                # quantize the candidate depth UP to the declared tier —
                # uniformly, solo and batched alike, so co-batching
                # requests of differing k stays bit-identical to solo
                # runs (both scan at the tier; _shape_results trims each
                # caller to its own k) and the compiled-program universe
                # per path is bounded by the declared grid
                fetch_k = _perf.bucket_fetch_k(fetch_k)
            for name, queries in req.vectors.items():
                if req.ctx is not None:
                    req.ctx.check()
                field = prev = _ivf_ops.phase(f"engine.search.{name}",
                                              request_only=True, after=prev)
                index = self.indexes[name]
                queries = np.asarray(queries)  # lint: allow[host-sync] host-side input normalization, queries arrive as lists/host arrays
                if queries.ndim == 1:
                    queries = queries[None, :]
                queries = index.decode_input(
                    queries.reshape(queries.shape[0], index.input_dim)
                )
                queries_by_field[name] = queries
                b_rows = int(queries.shape[0])
                q_run = queries
                if self.shape_buckets:
                    # pad the row axis up to the declared bucket with a
                    # REAL row (cosine normalisation of a zero row is
                    # degenerate); every scan path is per-query-row, so
                    # slicing the pad rows back off preserves results
                    bb = _perf.bucket_rows(b_rows)
                    if bb != b_rows:
                        q_run = np.concatenate(
                            [queries,
                             np.repeat(queries[-1:], bb - b_rows, axis=0)],
                            axis=0,
                        )
                    self.pad_real_rows += b_rows
                    self.pad_padded_rows += bb
                    self.pad_waste_bytes += _perf.padding_waste_bytes(
                        b_rows, bb, int(queries.shape[1])
                    )
                if capture is not None:
                    capture.rows = b_rows
                    capture.bucket_rows = int(q_run.shape[0])
                store = self.vector_stores[name]
                use_index = index.trained and not req.brute_force
                if use_index:
                    if index.indexed_count < store.count:
                        # realtime pump: absorb rows that arrived since
                        # the last pass (reference: AddRTVecsToIndex)
                        index.absorb(store.count)
                    scores, ids = index.search(
                        q_run, fetch_k, valid, req.index_params or None
                    )
                else:
                    # brute-force fallback below training threshold
                    # (reference: engine.cc:280-302)
                    from vearch_tpu.index.flat import FlatIndex

                    flat = FlatIndex(
                        IndexParams(metric_type=index.metric), store
                    )
                    scores, ids = flat.search(q_run, fetch_k, valid)
                per_field[name] = (scores[:b_rows], ids[:b_rows])
                if tracing:
                    # close the open dispatch window: device work for
                    # this field is done (device_get already blocked)
                    _ivf_ops.capture_mark()
                    req.trace[f"search_{name}_ms"] = round(
                        (field.close() - field.t0) * 1e3, 3
                    )

            if req.ctx is not None:
                req.ctx.check()
            merge = _ivf_ops.phase("engine.merge", request_only=True,
                                   after=prev)
            merged = self._merge_fields(per_field, queries_by_field, req)
            if tracing:
                req.trace["merge_ms"] = round(
                    (merge.close() - merge.t0) * 1e3, 3)
            shape = _ivf_ops.phase("engine.shape", request_only=True,
                                   after=merge)
            results = self._shape_results(merged, req)
            if tracing:
                t_end = shape.close()
                req.trace["shape_ms"] = round((t_end - shape.t0) * 1e3, 3)
                req.trace["total_ms"] = round((t_end - filt.t0) * 1e3, 3)
                req.trace["doc_count"] = self.doc_count
            return results
        finally:
            if capture is not None:
                _ivf_ops.end_capture()
                self._record_dispatch_trace(req, capture)

    def _record_dispatch_trace(self, req, capture) -> None:
        """Fold the per-request dispatch capture (dispatches and host
        windows) into req.trace: measured dispatches (tags, per-dispatch wall ms) next
        to the perf model's prediction for the matched serving path, so
        model drift is visible per request (ROADMAP: perf gates as live
        signals). `_phase_spans` is consumed by cluster/ps.py to emit
        engine/kernel child spans."""
        from vearch_tpu.ops import perf_model

        trace = req.trace
        if trace is None:
            return
        tags = capture.tags
        trace["dispatches"] = tags
        trace["dispatch_count"] = len(tags)
        if capture.kernels:
            trace["dispatch_kernels"] = dict(capture.kernels)
        for ev in capture.events:
            if ev.t1 is not None:
                key = f"dispatch_{ev.tag}_ms"
                trace[key] = round(
                    trace.get(key, 0.0) + (ev.t1 - ev.t0) * 1e3, 3
                )
        path = perf_model.path_for_dispatches(tags)
        if path is not None:
            trace["perf_path"] = path
            trace["predicted_dispatches"] = list(
                perf_model.DOCUMENTED_DISPATCHES[path]
            )
        trace["predicted_scan_bytes"] = sum(
            self._predicted_scan_bytes(name) for name in req.vectors
        )
        # extend, don't replace: the microbatcher may have noted its
        # queue wait on this trace before the search ran. Capture stamps
        # are monotonic; mono_us anchors them to the epoch. Every row
        # carries the CPU time of the thread that measured it (the
        # capture's) and that thread's id.
        from vearch_tpu.utils import mono_us

        tid = capture.tid
        spans = list(trace.get("_phase_spans") or [])
        spans.extend(
            [f"kernel.{ev.tag}", mono_us(ev.t0), int((ev.t1 - ev.t0) * 1e6),
             {"rows": ev.rows, "bucket_rows": ev.bucket_rows,
              # launch_us: the jitted call returned (program enqueued,
              # query uploaded); the rest of the window waits for the
              # device. Only the sites that stamp it carry it, with
              # launch_cpu_us: the thread's CPU up to it.
              **({"launch_us": int((ev.t_launch - ev.t0) * 1e6),
                  "launch_cpu_us": ev.launch_cpu_ns // 1000}
                 if ev.t_launch is not None else {}),
              # a full-scan site: the scores a query that the widest
              # sort of the program's selection takes
              **({"select_width": ev.select_width}
                 if ev.select_width is not None else {})},
             ev.cpu_ns, tid]
            for ev in capture.events
            if ev.t1 is not None
        )
        spans.extend(
            [name, mono_us(t0), int((t1 - t0) * 1e6), tags or {}, cpu, tid]
            for name, t0, t1, tags, cpu in capture.phases
        )
        trace["_phase_spans"] = spans
        noted = {name.split(".", 1)[0] for name, *_ in capture.phases}
        if "mesh" in noted or any(t.startswith("sharded") for t in tags):
            info = self.mesh_info()
            if info is not None:
                trace["mesh"] = info
        if "tier" in noted:
            tinfo = self.tiering_info()
            if tinfo is not None:
                trace["tiering"] = tinfo

    def mesh_info(self) -> dict[str, Any] | None:
        """Aggregate mesh data-plane summary over the engine's vector
        fields (surfaced in /ps/stats and profile:true traces); None
        when no field serves through the mesh."""
        fields = {}
        for name, index in self.indexes.items():
            try:
                info = index.mesh_info()
            except Exception:
                info = None
            if info is not None:
                fields[name] = info
        if not fields:
            return None
        out: dict[str, Any] = {
            "devices": max(f["devices"] for f in fields.values()),
            "fields": fields,
        }
        return out

    def ivf_info(self) -> dict[str, Any] | None:
        """The published bucket table of each vector field whose index
        keeps one (index/ivf.py `ivf_info`; surfaced in /ps/stats and
        the `vearch_ps_ivf_publish` gauges); None when no field has
        published."""
        fields = {name: info for name, index in self.indexes.items()
                  if (info := index.ivf_info()) is not None}
        return {"fields": fields} if fields else None

    def refine_info(self) -> dict[str, Any] | None:
        """The three-stage refinement funnel of each vector field whose
        index serves one (index/binary.py `refine_info`; surfaced in
        /ps/stats); None when no field has served such a search."""
        fields = {name: info for name, index in self.indexes.items()
                  if (info := index.refine_info()) is not None}
        return {"fields": fields} if fields else None

    def select_info(self) -> dict[str, Any] | None:
        """The full-scan dispatches of each vector field by site and by
        how wide the widest sort of the program's selection was
        (index/ivf.py `select_info`; surfaced in /ps/stats); None when
        no field has made one."""
        fields = {name: info for name, index in self.indexes.items()
                  if (info := index.select_info()) is not None}
        return {"fields": fields} if fields else None

    def tiering_info(self) -> dict[str, Any] | None:
        """Aggregate tiered-storage summary over the engine's vector
        fields (surfaced in /ps/stats and profile:true traces); None
        when no field serves through the storage tiers."""
        fields: dict[str, Any] = {}
        for name, index in self.indexes.items():
            try:
                info = index.tiering_info()
            except Exception:
                info = None
            row_cache = getattr(self.vector_stores[name], "row_cache", None)
            if row_cache is not None:
                info = dict(info or {"kind": "disk_store"})
                info["row_cache"] = row_cache.stats()
            if info is not None:
                fields[name] = info
        if not fields:
            return None
        return {"fields": fields}

    def _predicted_scan_bytes(self, name: str) -> int:
        """Perf-model prediction of stage-1 scan HBM read bytes for one
        field (ops/perf_model.scan_traffic_bytes): the int8 mirror when
        one is published, else the raw store rows."""
        from vearch_tpu.ops import perf_model

        index = self.indexes[name]
        store = self.vector_stores[name]
        d = store.dimension
        mirror = getattr(index, "_mirror", None)
        try:
            if mirror is not None and getattr(mirror, "_h8", None) is not None:
                return perf_model.scan_traffic_bytes(
                    int(mirror._h8.shape[0]), d
                )
        except Exception:
            pass
        return int(store.count) * d * int(store.store_dtype.itemsize)

    def _exact_score(
        self, name: str, query: np.ndarray, docids: list[int]
    ) -> np.ndarray:
        """Host-side exact similarity scores for a small candidate set
        (union rescoring in the multi-field merge)."""
        from vearch_tpu.engine.types import MetricType

        store = self.vector_stores[name]
        vecs = np.stack([store.get(i) for i in docids])
        metric = self.indexes[name].metric
        dots = vecs @ query
        if metric is MetricType.INNER_PRODUCT:
            return dots
        if metric is MetricType.COSINE:
            qn = max(float(np.linalg.norm(query)), 1e-15)
            vn = np.maximum(np.linalg.norm(vecs, axis=1), 1e-15)
            return dots / (qn * vn)
        return -(np.sum((vecs - query) ** 2, axis=1))

    def _merge_fields(
        self,
        per_field: dict[str, tuple[np.ndarray, np.ndarray]],
        queries_by_field: dict[str, np.ndarray],
        req: SearchRequest,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Multi-vector-field rank merge with weights (reference:
        vector_manager.cc:748 docid-sorted merge + WeightedRanker).

        Candidates = union of per-field top lists; every candidate is then
        rescored *exactly* in every field, so a doc missing from one
        field's truncated list still gets its true weighted score."""
        if len(per_field) == 1:
            return next(iter(per_field.values()))
        names = list(per_field)
        b = per_field[names[0]][0].shape[0]
        out_scores = []
        out_ids = []
        for qi in range(b):
            union: set[int] = set()
            for name in names:
                _, ids = per_field[name]
                scores = per_field[name][0]
                union.update(
                    int(i)
                    for s, i in zip(scores[qi], ids[qi])
                    if i >= 0 and np.isfinite(s)
                )
            cand = sorted(union)
            if not cand:
                out_ids.append([-1] * req.k)
                out_scores.append([float("-inf")] * req.k)
                continue
            total = np.zeros(len(cand), dtype=np.float64)
            keep = np.ones(len(cand), dtype=bool)
            for name in names:
                w = req.field_weights.get(name, 1.0)
                sf = self._exact_score(
                    name, queries_by_field[name][qi], cand
                )
                if req.score_bounds and name in req.score_bounds:
                    # per-field window on the FIELD's own score, as the
                    # reference attaches min/max_score to each vector
                    # query — not to the fused total
                    from vearch_tpu.ops.distance import score_to_metric

                    lo, hi = req.score_bounds[name]
                    mf = np.asarray(score_to_metric(
                        np.asarray(sf), self.indexes[name].metric))
                    if lo is not None:
                        keep &= mf >= lo
                    if hi is not None:
                        keep &= mf <= hi
                total += w * sf
            total = np.where(keep, total, -np.inf)
            order = np.argsort(-total)[: req.k]
            ids_row = [
                cand[i] if np.isfinite(total[i]) else -1 for i in order
            ]
            sc_row = [float(total[i]) for i in order]
            pad = req.k - len(ids_row)
            out_ids.append(ids_row + [-1] * pad)
            out_scores.append(sc_row + [float("-inf")] * pad)
        return np.asarray(out_scores), np.asarray(out_ids)

    def _shape_results(
        self, merged: tuple[np.ndarray, np.ndarray], req: SearchRequest
    ) -> list[SearchResult]:
        from vearch_tpu.ops.distance import score_to_metric

        scores, ids = merged
        metric = self.indexes[next(iter(req.vectors))].metric
        # fully vectorised shaping: one score conversion, one key gather,
        # one column gather per field for the whole batch — the per-item
        # Python loop here was a measured chunk of e2e latency (r1
        # VERDICT weak-3)
        scores = np.asarray(scores)
        ids = np.asarray(ids)
        k = min(req.k, scores.shape[1])
        scores, ids = scores[:, :k], ids[:, :k]
        metric_scores = np.asarray(score_to_metric(scores, metric))
        want_fields = req.include_fields is None or bool(req.include_fields)
        ok = (ids >= 0) & np.isfinite(scores)
        if req.score_bounds and len(req.vectors) == 1:
            # single-field: the final score IS the field's score, so the
            # window applies here; multi-field requests already applied
            # per-field windows inside the rank merge
            los = [b[0] for b in req.score_bounds.values()
                   if b[0] is not None]
            his = [b[1] for b in req.score_bounds.values()
                   if b[1] is not None]
            if los:
                ok &= metric_scores >= max(los)
            if his:
                ok &= metric_scores <= min(his)
        flat_ids = ids[ok].astype(np.int64)
        keys = self.table.keys_for(flat_ids)
        if req.raw_results and not req.sort and not want_fields:
            # columnar serving shape: no per-item objects, scores stay
            # one numpy buffer end to end
            from vearch_tpu.engine.types import ColumnarSearchResults

            return ColumnarSearchResults(
                flat_keys=keys,
                counts=ok.sum(axis=1),
                scores=np.ascontiguousarray(metric_scores[ok],
                                            dtype=np.float32),
            )
        fields_list = (
            self.table.gather_rows(flat_ids, req.include_fields)
            if want_fields
            else [{}] * len(keys)
        )
        flat_scores = metric_scores[ok].tolist()
        sort_rows = self._sort_value_rows(
            req.sort, flat_ids, keys, flat_scores,
            fields_list if want_fields else None, req.include_fields)
        counts = ok.sum(axis=1).tolist()
        results = []
        pos = 0
        for c in counts:
            items = [
                SearchResultItem(key=keys[j], score=float(flat_scores[j]),
                                 fields=fields_list[j],
                                 sort_values=sort_rows[j]
                                 if sort_rows is not None else None)
                for j in range(pos, pos + c)
            ]
            if req.sort:
                self._order_items(items, req.sort, metric)
            results.append(SearchResult(items=items))
            pos += c
        return results

    def _sort_value_rows(
        self, specs: list[dict] | None, flat_ids: np.ndarray,
        keys: list[str], flat_scores: list[float],
        fields_list: list[dict] | None,
        include_fields: list[str] | None,
    ) -> list[list] | None:
        """Per-hit sort-value lists (spec order) for the whole flat
        batch. _score and _id come from the hit itself; scalar fields
        are read from the already-gathered projection when it covers
        them (the router auto-adds sort fields to non-empty
        projections, so the common case pays zero extra gathers) and
        fetched in one extra gather only for fields==[] requests."""
        if not specs:
            return None
        from vearch_tpu.engine.sort import ID_FIELD, SCORE_FIELD

        covered = (fields_list is not None
                   and (include_fields is None
                        or set(include_fields).issuperset(
                            s["field"] for s in specs
                            if s["field"] not in (ID_FIELD, SCORE_FIELD))))
        if covered:
            field_rows = fields_list
        else:
            scalar_fields = [s["field"] for s in specs
                             if s["field"] not in (ID_FIELD, SCORE_FIELD)]
            field_rows = (
                self.table.gather_rows(flat_ids, scalar_fields)
                if scalar_fields else [{}] * len(keys)
            )
        out = []
        for j in range(len(keys)):
            row = []
            for s in specs:
                f = s["field"]
                if f == SCORE_FIELD:
                    row.append(flat_scores[j])
                elif f == ID_FIELD:
                    row.append(keys[j])
                else:
                    row.append(field_rows[j].get(f))
            out.append(row)
        return out

    def _order_items(self, items: list, specs: list[dict], metric) -> None:
        """In-place order of one query's hits by the sort spec; ties
        break on metric-oriented score (L2 ascending, IP/cosine
        descending) then key, so the order is deterministic and
        partition-merge-stable."""
        from vearch_tpu.engine.sort import row_sort_key
        from vearch_tpu.engine.types import MetricType

        l2 = metric is MetricType.L2
        items.sort(key=row_sort_key(
            specs,
            lambda it: it.sort_values,
            tie_key=lambda it: ((it.score if l2 else -it.score), it.key),
        ))

    # -- persistence (reference: engine.cc:1217 Dump / :1293 Load) ----------

    def snapshot_state(self) -> dict:
        """Phase 1 of a dump: capture a consistent point-in-time view
        under the write lock. Cheap — pointer copies and stable views of
        append-only copy-on-grow arrays. The caller may then persist it
        lock-free with write_snapshot()."""
        with self._write_lock:
            return {
                "table": self.table.snapshot(),
                "bits": self.bitmap.snapshot(self.table.doc_count),
                "vecs": {
                    name: store.host_view()
                    for name, store in self.vector_stores.items()
                },
                "status": int(self.status),
            }

    # rows per segment before the tail-merge compaction kicks in, and the
    # max number of undersized trailing segments tolerated before they
    # are merged (LSM-ish: flush cost stays O(new rows) for normal
    # flushes; every MAX_SMALL_SEGMENTS-th small flush pays one merge)
    SEGMENT_TARGET_ROWS = 100_000
    MAX_SMALL_SEGMENTS = 8

    def _read_manifest(self, dirpath: str) -> list[dict]:
        """Validated, contiguous-from-zero segment list (or empty)."""
        path = os.path.join(dirpath, "MANIFEST.json")
        if not os.path.exists(path):
            return []
        try:
            with open(path) as f:
                segs = json.load(f)["segments"]
        except Exception:
            return []
        segs = sorted(segs, key=lambda s: s["start"])
        out, expect = [], 0
        for s in segs:
            if s["start"] != expect or not os.path.isdir(
                os.path.join(dirpath, "segments", s["name"])
            ):
                break
            out.append(s)
            expect = s["end"]
        return out

    def _write_segment(
        self, snap: dict, dirpath: str, start: int, end: int, in_place: bool
    ) -> dict:
        name = f"seg_{start:010d}_{end:010d}"
        final = os.path.join(dirpath, "segments", name)
        tmp = final + ".tmp"
        import shutil

        if os.path.isdir(tmp):
            shutil.rmtree(tmp)
        if os.path.isdir(final):
            # orphan from a crash between os.replace and the manifest
            # commit: rows are immutable, so a same-boundary segment has
            # identical content — but os.replace cannot rename onto a
            # non-empty dir, so drop it or every later dump wedges
            shutil.rmtree(final)
        os.makedirs(tmp)
        tsnap = snap["table"]
        np.savez(
            os.path.join(tmp, "table.npz"),
            **{n: arr[start:end] for n, arr in tsnap["fixed"].items()},
        )
        with open(os.path.join(tmp, "table.json"), "w") as f:
            json.dump({
                "keys": tsnap["keys"][start:end],
                "strings": {
                    k: v[start:end] for k, v in tsnap["strings"].items()
                },
            }, f)
        for fname, view in snap["vecs"].items():
            store = self.vector_stores[fname]
            if getattr(store, "durable_on_disk", False) and in_place:
                continue  # the store's own mmap is the durable payload
            arr = np.asarray(view[start:end])
            if arr.dtype.kind not in "fiu":
                # ml_dtypes (bfloat16) need pickle to round-trip npy;
                # widen to f32 so backups stay allow_pickle=False
                arr = arr.astype(np.float32)
            np.save(os.path.join(tmp, f"vectors_{fname}.npy"), arr)
        os.replace(tmp, final)
        return {"name": name, "start": start, "end": end}

    def write_snapshot(self, snap: dict, dirpath: str) -> None:
        """Phase 2: persist a snapshot_state() capture. Runs without any
        engine lock (a torn dump was the original bug; lock-free writes
        of the captured views are safe because stores never mutate rows
        in place).

        Segmented, append-only format (r2 VERDICT weak #5: the flat
        format rewrote every column per flush — O(N) per checkpoint at
        16M rows/chip). Rows are immutable once appended (updates append
        + soft-delete), so sealed segments never change: a flush writes
        ONE new segment covering rows since the last seal, rewrites only
        the small mutable artifacts (bitmap, index state, manifest), and
        commits via an atomic MANIFEST.json rename — a crash mid-flush
        leaves the previous manifest pointing at intact files (reference
        behavior: incremental RocksDB writes, storage_manager.h:21 +
        flush jobs, store_raft_job.go:97)."""
        os.makedirs(dirpath, exist_ok=True)
        os.makedirs(os.path.join(dirpath, "segments"), exist_ok=True)
        count = len(snap["table"]["keys"])
        in_place = bool(
            self.data_dir
            and os.path.commonpath(
                [os.path.abspath(dirpath), os.path.abspath(self.data_dir)]
            ) == os.path.abspath(self.data_dir)
        )

        segs = self._read_manifest(dirpath)
        while segs and segs[-1]["end"] > count:
            segs.pop()  # rewind (restore/truncation): reseal the tail
        sealed = segs[-1]["end"] if segs else 0
        # compaction: merge the undersized trailing run into this flush
        # once it gets long, so segment count stays ~count/target + 8
        small = 0
        while (
            small < len(segs)
            and (segs[-1 - small]["end"] - segs[-1 - small]["start"])
            < self.SEGMENT_TARGET_ROWS
        ):
            small += 1
        if small > self.MAX_SMALL_SEGMENTS:
            sealed = segs[len(segs) - small]["start"]
            del segs[len(segs) - small:]
        if sealed < count:
            segs.append(
                self._write_segment(snap, dirpath, sealed, count, in_place)
            )

        with open(os.path.join(dirpath, "schema.json"), "w") as f:
            json.dump(self.schema.to_dict(), f)
        np.save(os.path.join(dirpath, "bitmap.npy"), snap["bits"])
        for name, view in snap["vecs"].items():
            store = self.vector_stores[name]
            if getattr(store, "durable_on_disk", False) and in_place:
                # disk store dumping into its own data_dir: msync +
                # record the durable count instead of copying a
                # beyond-RAM file
                store.flush_disk(n=view.shape[0])
        for name, index in self.indexes.items():
            state = index.dump_state()
            if state:
                np.savez(os.path.join(dirpath, f"index_{name}.npz"), **state)
        with open(os.path.join(dirpath, "engine.json"), "w") as f:
            json.dump({"status": snap["status"]}, f)
        tmp = os.path.join(dirpath, "MANIFEST.json.tmp")
        with open(tmp, "w") as f:
            json.dump({"format": 2, "doc_count": count, "segments": segs}, f)
        os.replace(tmp, os.path.join(dirpath, "MANIFEST.json"))
        # GC segment dirs the (now-durable) manifest no longer references
        keep = {s["name"] for s in segs}
        segroot = os.path.join(dirpath, "segments")
        for nm in os.listdir(segroot):
            if nm not in keep:
                import shutil

                shutil.rmtree(os.path.join(segroot, nm), ignore_errors=True)

    def dump(self, dirpath: str | None = None) -> None:
        dirpath = dirpath or self.data_dir
        assert dirpath, "no data_dir configured"
        self.write_snapshot(self.snapshot_state(), dirpath)

    def load(self, dirpath: str | None = None) -> None:
        dirpath = dirpath or self.data_dir
        assert dirpath and os.path.exists(dirpath), f"no dump at {dirpath}"
        if os.path.exists(os.path.join(dirpath, "MANIFEST.json")):
            self._load_segmented(dirpath)
        else:  # legacy flat dump (pre-segment backups)
            self.table.load(os.path.join(dirpath, "table"))
            self.bitmap.load(os.path.join(dirpath, "bitmap.npy"))
            for name, store in self.vector_stores.items():
                store.load(os.path.join(dirpath, f"vectors_{name}.npy"))
        for name, index in self.indexes.items():
            p = os.path.join(dirpath, f"index_{name}.npz")
            if os.path.exists(p):
                index.load_state(dict(np.load(p, allow_pickle=False)))
        with open(os.path.join(dirpath, "engine.json")) as f:
            self.status = IndexStatus(json.load(f)["status"])
        if self._scalar_manager is not None:
            self._scalar_manager.rebuild_from_table(self.table)

    def _load_segmented(self, dirpath: str) -> None:
        segs = self._read_manifest(dirpath)
        self.bitmap.load(os.path.join(dirpath, "bitmap.npy"))
        keys: list[str] = []
        strings: dict[str, list] = {
            n: [] for n in self.table._strings
        }
        fixed_parts: dict[str, list[np.ndarray]] = {
            n: [] for n in self.table._fixed
        }
        for s in segs:
            sd = os.path.join(dirpath, "segments", s["name"])
            with open(os.path.join(sd, "table.json")) as f:
                meta = json.load(f)
            keys.extend(meta["keys"])
            for n in strings:
                part = meta["strings"].get(n)
                if part is None:
                    # segment predates this column (e.g. the hidden
                    # presence column): pad so lengths stay row-aligned
                    part = [None] * len(meta["keys"])
                strings[n].extend(part)
            data = np.load(os.path.join(sd, "table.npz"))
            for n in fixed_parts:
                fixed_parts[n].append(data[n])
        fixed = {
            n: (np.concatenate(parts) if parts
                else np.zeros(0, self.table._fixed[n].dtype))
            for n, parts in fixed_parts.items()
        }
        n_rows = len(keys)
        self.table.load_from_segments(
            keys, strings, fixed, self.bitmap.valid_mask(n_rows)
        )
        for name, store in self.vector_stores.items():
            paths = [
                p for s in segs
                if os.path.exists(p := os.path.join(
                    dirpath, "segments", s["name"], f"vectors_{name}.npy"))
            ]
            if paths:
                store.load_parts(paths)
            else:  # in-place disk store: roll back via its meta barrier
                store.load(os.path.join(dirpath, f"vectors_{name}.npy"))

    @classmethod
    def open(cls, dirpath: str) -> "Engine":
        with open(os.path.join(dirpath, "schema.json")) as f:
            schema = TableSchema.from_dict(json.load(f))
        eng = cls(schema, data_dir=dirpath)
        eng.load(dirpath)
        return eng
