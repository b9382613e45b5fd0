"""Raw vector column store with a device-resident mirror.

TPU-native re-design of the reference's RawVector hierarchy (reference:
internal/engine/vector/raw_vector.h:62; MemoryRawVector segments,
memory_raw_vector.cc). The reference grows mmap-able segments; TPU wants
one large static-shaped device array, so:

- host side: an append-only numpy buffer with capacity doubling (the
  durable source of truth — dump/load streams this, never device state);
- device side: a padded [capacity, d] jax array refreshed lazily. Appends
  land in a host "dirty tail"; the next search flushes the tail with a
  single `jax.lax.dynamic_update_slice` donation-style rebuild, so steady
  -state ingest costs one small H2D copy per refresh interval, not one
  per doc (mirrors the reference's realtime ingest pump,
  vector_manager.h:76 AddRTVecsToIndex);
- capacity doubling reallocates the device buffer (rare, amortised O(1));
- squared norms are cached device-side per refresh so the L2 hot path
  reads the base matrix exactly once per query batch.

`store_dtype` bfloat16 halves HBM traffic on the brute-force scan — the
TPU analogue of the reference's store-type choice (MemoryOnly vs RocksDB,
raw_vector.h:29 StoreParams).
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from vearch_tpu.ops import ivf as ivf_ops
from vearch_tpu.ops import perf_model
from vearch_tpu.ops.distance import host_sqnorms
from vearch_tpu.parallel.mesh import row_pack
from vearch_tpu.tools import lockcheck


class RawVectorStore:
    def __init__(
        self,
        dimension: int,
        store_dtype: str = "float32",
        init_capacity: int = 4096,
    ):
        self.dimension = dimension
        self.store_dtype = jnp.dtype(store_dtype)
        self._host = np.zeros((init_capacity, dimension), dtype=np.float32)
        self._n = 0
        self._device: jax.Array | None = None  # [capacity, d] store_dtype
        self._device_sqnorm: jax.Array | None = None  # [capacity] f32
        self._device_rows = 0  # rows already mirrored to device
        # the same three for the placement a row GATHER takes at a width
        # that is no multiple of 128 (`device_buffer(packed=True)`):
        # [capacity / pack, pack * d]; never placed at `row_pack` 1
        self._packed: jax.Array | None = None
        self._packed_sqnorm: jax.Array | None = None
        self._packed_rows = 0
        # concurrent first placements race: a second searcher (another
        # request thread, the shadow-recall sampler) saw `_device` set
        # and `_device_sqnorm` still None mid-placement and crashed, or
        # tail-flushed into a half-built buffer and served wrong rows
        # (found by the four-device chip_smoke phase). One leaf lock
        # serializes device placement, as Int8Mirror's does.
        self._flush_lock = lockcheck.make_lock("raw_store_flush")

    @property
    def count(self) -> int:
        return self._n

    @property
    def capacity(self) -> int:
        return self._host.shape[0]

    def add(self, vectors: np.ndarray) -> int:
        """Append [b, d] rows; returns the first assigned row id (== docid
        base, the engine keeps row id == docid)."""
        b = vectors.shape[0]
        assert vectors.shape[1] == self.dimension
        if self._n + b > self._host.shape[0]:
            new_cap = max(self._host.shape[0] * 2, self._n + b, 1024)
            grown = np.zeros((new_cap, self.dimension), dtype=np.float32)
            grown[: self._n] = self._host[: self._n]
            self._host = grown
        start = self._n
        self._host[start : start + b] = vectors
        self._n += b
        return start

    def placed_bytes(self) -> int:
        """Device bytes of the single-device placements that exist now:
        the buffer in each form that was asked for, with its squared
        norms (metadata: no sync)."""
        return sum(int(a.nbytes) for a in (
            self._device, self._device_sqnorm, self._packed,
            self._packed_sqnorm) if a is not None)

    def host_view(self) -> np.ndarray:
        """[n, d] float32 host rows (training / rerank / dump path)."""
        return self._host[: self._n]

    def get(self, docid: int) -> np.ndarray:
        return self._host[docid]

    def device_buffer(
        self, packed: bool = False
    ) -> tuple[jax.Array, jax.Array, int]:
        """Returns (base [capacity, d], base_sqnorm [capacity], n_rows).

        Flushes any dirty tail to the device. Rows >= n_rows are padding
        and must be masked by the caller. The buffer is rebuilt only when
        capacity changed; otherwise the tail lands via dynamic_update_slice
        on the existing device array.

        `packed`: the buffer as a program that GATHERS rows wants it
        (the exact rerank; ops/ivf.py `gather_rows`):
        `[capacity / pack, pack * d]`, `pack` = `parallel.mesh.row_pack(d)`
        consecutive rows a device row. The chip lays `[capacity, d]` out
        column-major when `d` is no multiple of 128 (960, 96): right for
        a matrix product, but a row gather then copies the whole store
        row-major in every dispatch (3.84 GB at 1M x 960). Whole 128-lane
        super-rows are row-major as placed. At `pack` 1 (128, 768) this
        IS the plain buffer; past it the store keeps a placement for each
        form that was asked for (a FLAT scan beside a rerank holds two)."""
        with self._flush_lock:
            return self._device_buffer_locked(packed)

    def _device_buffer_locked(
        self, packed: bool = False
    ) -> tuple[jax.Array, jax.Array, int]:
        pack = row_pack(self.dimension) if packed else 1
        form = "_packed" if pack > 1 else "_device"
        dev: jax.Array | None = getattr(self, form)
        sqn: jax.Array | None = getattr(self, form + "_sqnorm")
        rows: int = getattr(self, form + "_rows")
        # snapshot n once: a concurrent upsert may advance self._n while we
        # flush; rows past the snapshot flush on the next call
        n = self._n
        cap = self._host.shape[0]
        d = self.dimension
        # a placement holds whole super-rows: `placed` device rows
        placed = -(-cap // pack)
        if dev is None or dev.shape[0] != placed:
            t0 = time.monotonic()
            # let the old placement go BEFORE the new one goes up: a
            # doubled store beside its predecessor is 3x the old bytes
            # at the peak (11.5 GB of a 16 GB chip at 1M x 960); a
            # search still in flight keeps its own reference
            dev = sqn = None
            setattr(self, form, None)
            setattr(self, form + "_sqnorm", None)
            host = self._host
            if placed * pack != cap:  # a capacity of no whole super-rows
                host = np.zeros((placed * pack, d), dtype=np.float32)
                host[:cap] = self._host
            dev = jnp.asarray(host.reshape(placed, pack * d),
                              dtype=self.store_dtype)
            # norms of the rows AS STORED: for float32 the host rows are
            # those (and 7.68 GB does not come back down to be squared)
            stored = (self._host if self.store_dtype == jnp.float32
                      else np.asarray(dev).reshape(-1, d)[:cap])
            sqn = jnp.asarray(host_sqnorms(stored))
            # .nbytes is metadata — no host sync
            nbytes = int(dev.nbytes) + int(sqn.nbytes)
            perf_model.note_h2d_bytes(nbytes)
            rows = n
            # the whole store goes up again (first placement, or the
            # host array grew past its capacity after a write): seconds
            # at a million rows, paid by whichever search comes next
            ivf_ops.note_phase("engine.replace_raw", t0, time.monotonic(),
                               {"bytes": nbytes})
        elif rows < n:
            # whole super-rows: from the one that holds the first new
            # row (its older rows are rewritten as they are) to the one
            # that holds the last (host rows past n are zeros)
            lo = rows // pack * pack
            hi = min(-(-n // pack) * pack, cap)
            tail = jnp.asarray(self._host[lo:hi], dtype=self.store_dtype)
            perf_model.note_h2d_bytes(int(tail.nbytes))
            tail_sqn = jnp.asarray(host_sqnorms(np.asarray(tail)))
            if (hi - lo) % pack:  # the last super-row of an odd capacity
                tail = jnp.pad(tail, ((0, pack - (hi - lo) % pack), (0, 0)))
            dev = jax.lax.dynamic_update_slice(
                dev, tail.reshape(-1, pack * d), (lo // pack, 0))
            sqn = jax.lax.dynamic_update_slice(sqn, tail_sqn, (lo,))
            rows = n
        setattr(self, form, dev)
        setattr(self, form + "_sqnorm", sqn)
        setattr(self, form + "_rows", rows)
        return dev, sqn, n

    _sh_cache = None

    def device_buffer_sharded(self, mesh) -> tuple[jax.Array, jax.Array, int]:
        """Row-sharded raw buffer over the mesh "data" axis (rerank path
        of a mesh-spanning partition). Growth within the cached capacity
        tail-appends only the new rows per shard; the derived sqnorm
        column is maintained on device by the cache (sqnorm_of=0) so it
        stays bit-identical to a full rebuild.

        The buffer is placed as `[cap / pack, pack * d]`, `pack` =
        `parallel.mesh.row_pack(d)` consecutive rows a device row (a
        reshape of the contiguous host rows); at `pack` 1, `[cap, d]`.
        Only `ops/ivf.py` `gather_rows` reads it. sqnorm stays
        `[cap]`, n_rows logical."""
        from vearch_tpu.parallel.mesh import ShardedRowCache

        pack = row_pack(self.dimension)

        def packed(rows: np.ndarray) -> np.ndarray:
            return rows.astype(self.store_dtype).reshape(
                rows.shape[0] // pack, pack * self.dimension)

        def build(cap):
            host = np.zeros((cap, self.dimension), dtype=np.float32)
            host[: self._n] = self._host[: self._n]
            return (packed(host),)

        def append(lo, hi):
            win = np.zeros((hi - lo, self.dimension), dtype=np.float32)
            m = min(hi, self._host.shape[0]) - lo
            if m > 0:
                win[:m] = self._host[lo : lo + m]
            return (packed(win),)

        with self._flush_lock:
            if self._sh_cache is None:
                self._sh_cache = ShardedRowCache(
                    align=128, sqnorm_of=0, pack=pack)
            (base,), _ = self._sh_cache.get(mesh, self._n, build, append)
            return base, self._sh_cache.sqnorm, self._n

    # -- persistence ---------------------------------------------------------

    def dump(self, path: str) -> None:
        np.save(path, self.host_view())

    def load(self, path: str) -> None:
        if os.path.exists(path):
            data = np.load(path)
            self._host = data.copy()
            self._n = data.shape[0]
            self._device = self._packed = None
            self._device_rows = self._packed_rows = 0
            if self._sh_cache is not None:
                self._sh_cache.invalidate()

    def load_parts(self, paths: list[str]) -> None:
        """Restore from per-segment row slices in order (segmented dump
        format; Engine.load concatenates MANIFEST segments)."""
        if not paths:
            return
        parts = [np.load(p, mmap_mode="r") for p in paths]
        n = sum(p.shape[0] for p in parts)
        host = np.zeros((max(n, 1024), self.dimension), dtype=np.float32)
        off = 0
        chunk = 1 << 18  # stream from the mmap; never double peak RAM
        for p in parts:
            for lo in range(0, p.shape[0], chunk):
                hi = min(lo + chunk, p.shape[0])
                host[off + lo : off + hi] = p[lo:hi]
            off += p.shape[0]
        self._host = host
        self._n = n
        self._device = self._packed = None
        self._device_rows = self._packed_rows = 0
        if self._sh_cache is not None:
            self._sh_cache.invalidate()
