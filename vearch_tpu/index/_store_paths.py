"""Store-aware search primitives shared by the index types.

Two storage regimes exist (reference: raw_vector_factory.h MemoryOnly vs
RocksDB): device-mirrored RAM stores and mmap'd disk stores
(engine/disk_vector.py). Index hot paths branch here instead of each
reimplementing the disk case:

- `rerank_against_store`: exact rerank of candidate ids — against the
  HBM-resident raw buffer as placed for a row gather (`device_buffer(
  packed=True)`) for RAM stores, or via a host mmap gather + one
  [B, r, d] upload for disk stores;
- `disk_brute_force`: chunked exact scan streaming the mmap through the
  device in fixed-shape chunks (the FLAT / pre-training fallback for
  beyond-RAM stores; fixed chunk shape = one XLA compile).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from vearch_tpu.engine.types import MetricType
from vearch_tpu.ops import ivf as ivf_ops
from vearch_tpu.ops.distance import brute_force_search

_CHUNK = 262_144  # rows per device chunk for the streaming scan


def is_disk_store(store) -> bool:
    return bool(getattr(store, "durable_on_disk", False))


def rerank_against_store(
    store,
    q: np.ndarray,          # [B, d] f32 (normalized upstream if cosine)
    cand_i: jax.Array,      # [B, r] i32
    k: int,
    metric: MetricType,
) -> tuple[jax.Array, jax.Array]:
    k = min(k, int(cand_i.shape[1]))
    if is_disk_store(store):
        ci = np.asarray(cand_i)
        safe = np.maximum(ci, 0).astype(np.int64)
        vecs = np.asarray(
            store.get_rows(safe.ravel()), dtype=np.float32
        ).reshape(ci.shape[0], ci.shape[1], -1)
        return ivf_ops.exact_rerank_gathered(
            jnp.asarray(q, jnp.float32), jnp.asarray(ci),
            jnp.asarray(vecs), k, metric,
        )
    base, base_sqnorm, _ = store.device_buffer(packed=True)
    return ivf_ops.exact_rerank(
        jnp.asarray(q, dtype=base.dtype), cand_i, base, base_sqnorm,
        k, metric,
    )


def disk_brute_force(
    store,
    queries: np.ndarray,    # [B, d] f32
    k: int,
    valid_mask: np.ndarray | None,
    metric: MetricType,
) -> tuple[np.ndarray, np.ndarray]:
    """Exact scan of a disk store: stream fixed-shape chunks through the
    device, fold per-chunk top-k on host. Exactness matches FLAT."""
    n = store.count
    b = queries.shape[0]
    k_eff = min(k, max(n, 1))
    host = store.host_view()
    q = jnp.asarray(queries, jnp.float32)
    # chunk = next power of two >= n, capped: small tables pay O(n), not
    # a full 262k-row pad; compile count stays logarithmic in n
    chunk = 128
    while chunk < min(n, _CHUNK):
        chunk *= 2
    rows = np.zeros((chunk, store.dimension), dtype=np.float32)
    all_s: list[np.ndarray] = []
    all_i: list[np.ndarray] = []
    for lo in range(0, max(n, 1), chunk):
        hi = min(lo + chunk, n)
        rows[:] = 0.0
        rows[: hi - lo] = host[lo:hi]
        mask = np.zeros(chunk, dtype=bool)
        if valid_mask is None:
            mask[: hi - lo] = True
        else:
            mask[: hi - lo] = np.asarray(valid_mask[lo:hi], dtype=bool)
        s, i = brute_force_search(
            q, jnp.asarray(rows), jnp.asarray(mask), k_eff, metric,
        )
        s, i = jax.device_get((s, i))
        all_s.append(s)
        all_i.append(np.where(i >= 0, i + lo, -1))
    s_cat = np.concatenate(all_s, axis=1)
    i_cat = np.concatenate(all_i, axis=1)
    order = np.argsort(-s_cat, axis=1)[:, :k]
    top_s = np.take_along_axis(s_cat, order, axis=1)
    top_i = np.take_along_axis(i_cat, order, axis=1)
    if top_s.shape[1] < k:
        pad = k - top_s.shape[1]
        top_s = np.pad(top_s, ((0, 0), (0, pad)),
                       constant_values=float("-inf"))
        top_i = np.pad(top_i, ((0, 0), (0, pad)), constant_values=-1)
    return top_s, top_i
