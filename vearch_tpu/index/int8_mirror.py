"""Docid-ordered int8 device mirror (shared by the scan-based indexes).

Append-only host arrays (codes, per-row scale, squared norm) with a
lazily-flushed device copy — the same tail-flush pattern as
RawVectorStore.device_buffer, for quantized payloads. Rows are int8 per-
row-scaled approximations; scoring dequantises inside the matmul kernel
(ops/ivf.py int8_scan_candidates).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from vearch_tpu.ops import perf_model
from vearch_tpu.parallel.mesh import row_pack
from vearch_tpu.tools import lockcheck


def quantize_rows(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-row symmetric int8 quantization; returns (q8, scale, vsq)."""
    scale = np.maximum(np.abs(rows).max(axis=1) / 127.0, 1e-12).astype(
        np.float32
    )
    q8 = np.clip(np.rint(rows / scale[:, None]), -127, 127).astype(np.int8)
    deq = q8.astype(np.float32) * scale[:, None]
    vsq = np.sum(deq * deq, axis=1).astype(np.float32)
    return q8, scale, vsq


def quantize_rows_int4(
    rows: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-row symmetric int4 quantization, nibble-packed.

    Layout contract (ops/ivf.py unpack_int4): dims [0, d/2) in the low
    nibble, dims [d/2, d) in the high nibble — concat, not interleave.
    Returns (packed [n, d/2] uint8, scale, vsq of the DEQUANTIZED rows).
    """
    d = rows.shape[1]
    assert d % 2 == 0, "int4 storage needs an even dimension"
    scale = np.maximum(np.abs(rows).max(axis=1) / 7.0, 1e-12).astype(
        np.float32
    )
    q4 = np.clip(np.rint(rows / scale[:, None]), -7, 7).astype(np.int8)
    deq = q4.astype(np.float32) * scale[:, None]
    vsq = np.sum(deq * deq, axis=1).astype(np.float32)
    lo = q4[:, : d // 2] & 0xF
    hi = q4[:, d // 2 :] & 0xF
    packed = (lo | (hi << 4)).astype(np.uint8)
    return packed, scale, vsq


class Int8Mirror:
    """Compressed device mirror; `storage` picks the tier:
    - "int8" (default): 1 byte/dim, ~0.8% row-max quantization error;
    - "int4": 0.5 byte/dim — HALF the resident HBM per row (the usual
      rows-per-chip limiter), ~7% row-max error that the exact rerank
      stage absorbs;
    - "bits": 1 BIT/dim packed sign planes (ops/binary_scan.py
      pack_sign_rows) — the stage-0 tier of the progressive refinement
      chain, 8x denser than int8's row payload; selection-grade scores
      that the int8 + exact refinement stages restore.
    """

    def __init__(self, dimension: int, storage: str = "int8"):
        self.dimension = dimension
        self.storage = str(storage).lower()
        if self.storage not in ("int8", "int4", "bits"):
            raise ValueError(f"unknown mirror storage {storage!r}")
        if self.storage == "int4" and dimension % 2 != 0:
            raise ValueError("int4 mirror storage needs an even dimension")
        if self.storage == "int8":
            width, dt = dimension, np.int8
        elif self.storage == "int4":
            width, dt = dimension // 2, np.uint8
        else:  # bits: byte-padded packed sign planes
            width, dt = -(-dimension // 8), np.uint8
        self._row_width = width
        self._row_dtype = dt
        self._h8 = np.zeros((0, width), dtype=dt)
        self._h_scale = np.zeros(0, dtype=np.float32)
        self._h_vsq = np.zeros(0, dtype=np.float32)
        self._n = 0
        self._d8: jax.Array | None = None
        self._d_scale: jax.Array | None = None
        self._d_vsq: jax.Array | None = None
        self._d_rows = 0   # rows of the payload `_d8` holds
        self._dc_rows = 0  # rows of the two columns
        # the row payload as a program that GATHERS rows takes it
        # (`flush(packed=True)`); never placed at pack 1
        self._dp8: jax.Array | None = None
        self._dp_rows = 0
        # append vs flush race: a concurrent append may REPLACE the
        # host arrays (capacity growth) while flush reads them — the
        # tail-flush would mix old and new buffers. One leaf lock
        # serializes host-array mutation against device placement.
        self._flush_lock = lockcheck.make_lock("mirror_flush")

    @property
    def count(self) -> int:
        return self._n

    def device_bytes(self) -> int:
        """Modeled resident HBM bytes of the flushed mirror: compressed
        rows + per-row scale + per-row ||v||^2, at the 512-aligned
        capacity (ops/perf_model.py mirror_footprint_bytes)."""
        cap = self._h8.shape[0]
        return cap * self._row_width + 2 * cap * 4

    def placed_bytes(self) -> int:
        """Device bytes of what is placed now: the payload in each form
        that was asked for, and the two columns (metadata: no sync)."""
        return sum(int(a.nbytes) for a in (
            self._d8, self._dp8, self._d_scale, self._d_vsq)
            if a is not None)

    def append_quantized(
        self, q8: np.ndarray, scale: np.ndarray, vsq: np.ndarray,
        start: int | None = None,
    ) -> None:
        """Write rows at [start, start+b) (default: append at count)."""
        with self._flush_lock:
            self._append_locked(q8, scale, vsq, start)

    def reserve(self, rows: int) -> None:
        """Room for `rows` rows before they arrive in pieces: a caller
        that knows the total (an absorb that quantises a piece at a
        time) gets the capacity ONE append of them all would, not the
        next doubling above it (1,048,576 for 1,000,000 rows: 4.8 % more
        rows in every scan)."""
        with self._flush_lock:
            self._grow_locked(rows)

    def _grow_locked(self, need: int) -> None:
        if self._h8.shape[0] >= need:
            return
        # capacity stays 512-aligned: the two-stage top-k takes a
        # score row in whole blocks of 128 (ops/ivf.py BLOCK), and
        # a ragged row falls back to one sort of the whole row
        cap = max(need, self._h8.shape[0] * 2, 1024)
        cap = -(-cap // 512) * 512
        g8 = np.zeros((cap, self._row_width), dtype=self._row_dtype)
        gs = np.zeros(cap, dtype=np.float32)
        gv = np.zeros(cap, dtype=np.float32)
        g8[: self._n] = self._h8[: self._n]
        gs[: self._n] = self._h_scale[: self._n]
        gv[: self._n] = self._h_vsq[: self._n]
        self._h8, self._h_scale, self._h_vsq = g8, gs, gv

    def _append_locked(
        self, q8: np.ndarray, scale: np.ndarray, vsq: np.ndarray,
        start: int | None,
    ) -> None:
        start = self._n if start is None else start
        need = start + q8.shape[0]
        self._grow_locked(need)
        sl = slice(start, need)
        self._h8[sl] = q8
        self._h_scale[sl] = scale
        self._h_vsq[sl] = vsq
        self._n = max(self._n, need)
        # rows below the mirrored high-water mark were overwritten
        # (re-absorb after load_state): force re-upload from `start`
        self._d_rows = min(self._d_rows, start)
        self._dc_rows = min(self._dc_rows, start)
        self._dp_rows = min(self._dp_rows, start)
        if self._sh_cache is not None:
            self._sh_cache.lower_rows(start)

    def append(self, rows: np.ndarray, start: int | None = None) -> None:
        if self.storage == "bits":
            from vearch_tpu.ops.binary_scan import pack_sign_rows

            quant = pack_sign_rows
        else:
            quant = (
                quantize_rows if self.storage == "int8"
                else quantize_rows_int4
            )
        self.append_quantized(*quant(rows), start=start)

    def flush_sharded(self, mesh) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Device views row-sharded over the mesh "data" axis — one
        logical partition spanning all chips (the capacity regime: rows
        beyond a single chip's HBM). Rows are padded so every shard is
        512-aligned (whole blocks for the two-stage top-k). Growth
        within the cached capacity tail-appends per shard (one H2D per
        touched device of only the new rows); a full re-place happens
        only on capacity change — realtime absorb on a mesh partition
        stays incremental.
        """
        if self._sh_cache is None:
            from vearch_tpu.parallel.mesh import ShardedRowCache

            self._sh_cache = ShardedRowCache(align=512)

        def build(cap):
            h8 = np.zeros((cap, self._row_width), dtype=self._row_dtype)
            hs = np.zeros(cap, dtype=np.float32)
            hv = np.zeros(cap, dtype=np.float32)
            n = self._n
            h8[:n] = self._h8[:n]
            hs[:n] = self._h_scale[:n]
            hv[:n] = self._h_vsq[:n]
            return h8, hs, hv

        def append(lo, hi):
            return (
                np.ascontiguousarray(self._h8[lo:hi]),
                np.ascontiguousarray(self._h_scale[lo:hi]),
                np.ascontiguousarray(self._h_vsq[lo:hi]),
            )

        with self._flush_lock:
            arrays, _ = self._sh_cache.get(mesh, self._n, build, append)
        return arrays

    _sh_cache = None

    def flush(
        self, packed: bool = False
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Device views [cap, w] / [cap] / [cap]; rows >= count are padding.

        `packed`: the row payload as a program that GATHERS rows from it
        wants it (the three-stage chain's stage 1; ops/ivf.py
        `gather_rows`): `[cap / pack, pack * w]`, `pack` =
        `parallel.mesh.row_pack(w)` consecutive rows a device row. At a
        width that is no multiple of 128 the chip lays `[cap, w]` out
        column-major, which a matrix product wants and a row gather
        copies whole, in every dispatch. At `pack` 1 this is the plain
        view; past it the payload is placed once for each form that was
        asked for. The two columns are the same arrays either way."""
        with self._flush_lock:
            return self._flush_locked(packed)

    def _placed(self, dev: jax.Array | None, done: int, host: np.ndarray,
                pack: int = 1) -> jax.Array:
        """`host` ([cap, w] or [cap]) on the device, as
        `[cap / pack, pack * w]`: placed whole when the capacity changed,
        else the rows from `done` on appended (whole super-rows: the one
        that holds row `done` goes up again as it is; the capacity is
        512-aligned and host rows past the count are zeros)."""
        n = self._n
        view = host.reshape(host.shape[0] // pack, -1) if pack > 1 else host
        if dev is None or dev.shape[0] != view.shape[0]:
            dev = jnp.asarray(view)
            # .nbytes is metadata — no host sync
            perf_model.note_h2d_bytes(int(dev.nbytes))
        elif done < n:
            lo = done // pack * pack
            tail = host[lo:-(-n // pack) * pack]
            perf_model.note_h2d_bytes(int(tail.nbytes))
            if pack > 1:
                tail = tail.reshape(-1, view.shape[1])
            dev = jax.lax.dynamic_update_slice(
                dev, jnp.asarray(tail),
                (lo // pack,) + (0,) * (view.ndim - 1))
        return dev

    def _flush_locked(
        self, packed: bool = False
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        n = self._n
        self._d_scale = self._placed(self._d_scale, self._dc_rows,
                                     self._h_scale)
        self._d_vsq = self._placed(self._d_vsq, self._dc_rows, self._h_vsq)
        self._dc_rows = n
        pack = row_pack(self._row_width) if packed else 1
        if pack == 1:
            self._d8 = self._placed(self._d8, self._d_rows, self._h8)
            self._d_rows = n
            return self._d8, self._d_scale, self._d_vsq
        self._dp8 = self._placed(self._dp8, self._dp_rows, self._h8, pack)
        self._dp_rows = n
        return self._dp8, self._d_scale, self._d_vsq
