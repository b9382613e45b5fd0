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
        self._d_rows = 0
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

    def append_quantized(
        self, q8: np.ndarray, scale: np.ndarray, vsq: np.ndarray,
        start: int | None = None,
    ) -> None:
        """Write rows at [start, start+b) (default: append at count)."""
        with self._flush_lock:
            self._append_locked(q8, scale, vsq, start)

    def _append_locked(
        self, q8: np.ndarray, scale: np.ndarray, vsq: np.ndarray,
        start: int | None,
    ) -> None:
        start = self._n if start is None else start
        need = start + q8.shape[0]
        if self._h8.shape[0] < need:
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
        sl = slice(start, need)
        self._h8[sl] = q8
        self._h_scale[sl] = scale
        self._h_vsq[sl] = vsq
        self._n = max(self._n, need)
        # rows below the mirrored high-water mark were overwritten
        # (re-absorb after load_state): force re-upload from `start`
        if start < self._d_rows:
            self._d_rows = start
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

    def flush(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Device views [cap, d] / [cap] / [cap]; rows >= count are padding."""
        with self._flush_lock:
            return self._flush_locked()

    def _flush_locked(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        n = self._n
        cap = self._h8.shape[0]
        if self._d8 is None or self._d8.shape[0] != cap:
            self._d8 = jnp.asarray(self._h8)
            self._d_scale = jnp.asarray(self._h_scale)
            self._d_vsq = jnp.asarray(self._h_vsq)
            # .nbytes is metadata — no host sync
            perf_model.note_h2d_bytes(
                int(self._d8.nbytes) + int(self._d_scale.nbytes)
                + int(self._d_vsq.nbytes)
            )
            self._d_rows = n
        elif self._d_rows < n:
            sl = slice(self._d_rows, n)
            perf_model.note_h2d_bytes(
                int(self._h8[sl].nbytes) + int(self._h_scale[sl].nbytes)
                + int(self._h_vsq[sl].nbytes)
            )
            self._d8 = jax.lax.dynamic_update_slice(
                self._d8, jnp.asarray(self._h8[sl]), (self._d_rows, 0)
            )
            self._d_scale = jax.lax.dynamic_update_slice(
                self._d_scale, jnp.asarray(self._h_scale[sl]), (self._d_rows,)
            )
            self._d_vsq = jax.lax.dynamic_update_slice(
                self._d_vsq, jnp.asarray(self._h_vsq[sl]), (self._d_rows,)
            )
            self._d_rows = n
        return self._d8, self._d_scale, self._d_vsq
