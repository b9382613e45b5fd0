"""Device mesh construction for multi-chip partitions.

The reference scales across machines with partition sharding + raft
replication (reference: SURVEY.md §2.3 — murmur3 slot sharding,
client-side scatter/gather). Within one partition server, this module adds
the axis the reference never had: a JAX device mesh over local TPU chips,
with the vector matrix row-sharded ("data" axis) and the query batch
sharded ("query" axis). Collectives ride ICI:

- search: per-shard top-k, then all_gather over "data" + re-top-k — the
  cross-chip merge never leaves the device (SURVEY.md §2.4: TPU-native
  equivalent of the router's host-side merge, pushed down to ICI);
- k-means training: psum of per-shard partial sums ("data" axis) — the
  classic data-parallel reduction.
"""

from __future__ import annotations

import functools
import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from vearch_tpu.ops import perf_model


def default_mesh() -> Mesh:
    """Process-wide all-devices mesh, rows on "data" (cached: mesh
    identity matters for jit cache hits)."""
    return make_mesh(query_axis=1)


@functools.lru_cache(maxsize=32)
def _mesh_cached(n: int, data_axis: int, query_axis: int) -> Mesh:
    dev_array = np.asarray(jax.devices()[:n]).reshape(data_axis, query_axis)
    return Mesh(dev_array, axis_names=("data", "query"))


def make_mesh(
    n_devices: int | None = None,
    data_axis: int | None = None,
    query_axis: int = 1,
) -> Mesh:
    """2D mesh ("data", "query") over the first n devices.

    Default puts all devices on "data" (row sharding) — the right shape
    for search serving where the DB dwarfs the query batch.

    Meshes are cached per (n, data_axis, query_axis): the shard_map
    program builders in parallel/sharded.py key their lru_caches on mesh
    IDENTITY, so a fresh Mesh per engine publish would retrace every
    sharded program and blow past the zero-new-programs perf gates.
    """
    n = min(n_devices or len(jax.devices()), len(jax.devices()))
    if data_axis is None:
        data_axis = n // query_axis
    assert data_axis * query_axis == n, (
        f"mesh {data_axis}x{query_axis} != {n} devices"
    )
    return _mesh_cached(n, data_axis, query_axis)


def mesh_from_shape(shape) -> Mesh:
    """Resolve a user-facing ``mesh_shape`` knob to a cached mesh.

    Accepts ``"4x2"`` strings, ``(data, query)`` pairs, a bare device
    count (all on "data"), or None/"" for :func:`default_mesh`. This is
    the single parse point for the engine/apply_config and index-params
    surfaces, so every layer lands on the SAME cached Mesh object and
    the shard_map program caches (keyed on mesh identity) stay warm.
    """
    if shape in (None, "", "auto", "default"):
        return default_mesh()
    if isinstance(shape, str):
        parts = shape.lower().split("x")
        if len(parts) == 1:
            return make_mesh(int(parts[0]))
        da, qa = (int(p) for p in parts[:2])
        return make_mesh(da * qa, data_axis=da, query_axis=qa)
    if isinstance(shape, (list, tuple)):
        da, qa = int(shape[0]), int(shape[1])
        return make_mesh(da * qa, data_axis=da, query_axis=qa)
    return make_mesh(int(shape))


def shard_rows(mesh: Mesh, x, pad_value=0):
    """Place a host [N, ...] array row-sharded over the "data" axis,
    padding N up to a multiple of the axis size. Returns (device_array,
    orig_n)."""
    n_shards = mesh.shape["data"]
    n = x.shape[0]
    rem = (-n) % n_shards
    if rem:
        pad = np.full((rem,) + x.shape[1:], pad_value, dtype=x.dtype)
        x = np.concatenate([np.asarray(x), pad], axis=0)
    sharding = NamedSharding(mesh, P("data", *([None] * (x.ndim - 1))))
    # .nbytes is metadata on both numpy and jax arrays — no host sync
    perf_model.note_h2d_bytes(int(getattr(x, "nbytes", 0)))
    # the host array goes to the devices shard by shard: staged through
    # jnp.asarray the WHOLE array sat on device 0 first (0.82 GB peak
    # there for a 1M x 96 partition whose shards are 0.12 GB; a corpus
    # one chip cannot hold would not have been placed at all)
    return jax.device_put(x, sharding), n


def shard_queries(mesh: Mesh, q):
    """Place a host [B, d] query batch sharded over the "query" axis
    (replicated over "data")."""
    n_shards = mesh.shape["query"]
    b = q.shape[0]
    rem = (-b) % n_shards
    if rem:
        q = np.concatenate(
            [np.asarray(q), np.zeros((rem, q.shape[1]), dtype=q.dtype)], axis=0
        )
    sharding = NamedSharding(mesh, P("query", None))
    perf_model.note_h2d_bytes(int(getattr(q, "nbytes", 0)))
    return jax.device_put(q, sharding), b


def row_pack(d: int) -> int:
    """Rows of a row-sharded `[N, d]` raw store that are placed side by
    side as one super-row, `[N / pack, pack * d]`: the least count that
    makes the minor dimension whole 128-lane groups, for `d` a multiple
    of 16 (96 -> 4, 64 -> 2, 32 -> 4); 1 for a multiple of 128 and for
    every width that would need more than 8 (100 -> 32).

    Why: the chip lays a minor dimension under 128 lanes out
    column-major (`f32[N, 96]{0,1:T(8,128)}`), and a program that gathers
    ROWS from it (the exact rerank) first copies the whole shard
    row-major, in every dispatch. `[N / 4, 384]` is row-major as placed;
    the rerank gathers super-row `id // pack` and keeps sub-row
    `id % pack` (parallel/sharded.py `_gather_rows`). Arrays that feed a
    matrix product (mirror, bit planes) are NOT packed: for a product
    column-major is what the chip wants."""
    if d % 128 == 0:
        return 1
    pack = 128 // math.gcd(d, 128)
    return pack if pack <= 8 else 1


def replicate(mesh: Mesh, x):
    spec = P(*([None] * np.ndim(x)))
    perf_model.note_h2d_bytes(int(getattr(x, "nbytes", 0)))
    return jax.device_put(x, NamedSharding(mesh, spec))


@functools.lru_cache(maxsize=16)
def _tail_update_fn(ndim: int, with_sqnorm: bool, pack: int = 1):
    """Per-device tail writer: dynamic_update_slice of the new rows into
    one shard's slab (NOT donated — a concurrent search may still hold
    the previous buffer; the device-side copy is the price of lock-free
    reads). Traced `off` so append offsets never retrace. The derived
    sqnorm tail arrives pre-computed host-side (ops/distance
    host_sqnorms) so every placement path lands the identical column.
    `off` counts the slab's own rows: super-rows of `pack` where the
    slab is packed, and the sqnorm column, one entry a logical row,
    starts at `off * pack`."""
    from vearch_tpu.ops.perf_model import register_jit

    def upd(dst, tail, off, sq=None, sq_tail=None):
        idx = (off,) + (0,) * (ndim - 1)
        out = jax.lax.dynamic_update_slice(dst, tail, idx)
        if sq is None:
            return out
        sq_off = off if pack == 1 else off * pack
        return out, jax.lax.dynamic_update_slice(sq, sq_tail, (sq_off,))

    fn = jax.jit(upd)
    tag = f"{ndim}d{',sqnorm' if with_sqnorm else ''}" \
        f"{f',pack{pack}' if pack > 1 else ''}"
    return register_jit(f"mesh.tail_append[{tag}]", fn)


class ShardedRowCache:
    """Grow-only cache of host row arrays placed row-sharded on a mesh.

    One invalidation point for every sharded device buffer (int8 mirror,
    raw rerank base, ...): `get` rebuilds when capacity changed, and
    TAIL-APPENDS when rows merely grew within the cached capacity and
    the caller supplies `append_host_fn` — one H2D per touched device of
    only the new rows, never a full re-place (realtime absorb on a mesh
    partition). `lower_rows` must be called when rows BELOW the
    high-water mark were overwritten (re-absorb, engine load) so the
    next get re-places instead of serving stale rows; `invalidate` drops
    everything.

    `sqnorm_of=i` maintains a derived [cap] f32 squared-norm column of
    arrays[i] (`self.sqnorm`), kept in lockstep through both rebuilds
    and tail-appends — the rerank base needs it and computing it host-
    side would break bit-equality with the single-device path.

    `stats` counts rebuilds / appends / H2D bytes so the perf gates can
    assert absorb never re-places the full buffer.

    The cache is keyed on mesh IDENTITY, so a runtime ``mesh_shape``
    change (engine apply_config -> index params -> mesh_from_shape)
    re-places every buffer onto the new mesh on the next get() with no
    explicit invalidation — the old mesh's placement is simply dropped.

    `pack` (:func:`row_pack`; the raw store's, 1 everywhere else) places
    `pack` logical rows as one device row: the host functions hand
    `[rows / pack, pack * d]` views, and `n`, capacities, windows and
    shard ownership stay in LOGICAL rows — `align` is a multiple of
    every pack, so a window or a shard offset is whole super-rows. Only
    the slices and offsets that touch a device array are divided.
    """

    def __init__(self, align: int, sqnorm_of: int | None = None,
                 pack: int = 1):
        assert align % pack == 0, (align, pack)
        self.align = align
        self.sqnorm_of = sqnorm_of
        self.pack = pack
        self._key = None
        self._rows = 0
        self.arrays: tuple | None = None
        self.sqnorm: jax.Array | None = None
        self.stats = {"rebuilds": 0, "appends": 0, "h2d_bytes": 0}

    def capacity(self, mesh: Mesh, n: int) -> int:
        """Sharded capacity for n rows: align*n_shards units, grown
        GEOMETRICALLY past the currently-placed capacity so realtime
        absorb amortizes to tail-appends (a tight capacity would force
        a full re-place every time n crossed a unit boundary)."""
        unit = self.align * mesh.shape["data"]
        need = -(-max(n, 1) // unit) * unit
        if self._key is not None and self._key[0] == id(mesh):
            cur = self._key[1]
            if cur >= need:
                return cur
            return max(need, 2 * cur)
        return need

    def get(self, mesh: Mesh, n: int, build_host_fn, append_host_fn=None):
        """build_host_fn(cap) -> tuple of host arrays with cap rows;
        append_host_fn(lo, hi) -> tuple of host arrays with hi-lo rows
        (rows [lo, hi) of each cached array); both as
        `[rows / pack, pack * d]`. Returns (device_arrays, rebuilt)."""
        cap = self.capacity(mesh, n)
        key = (id(mesh), cap)
        rebuilt = False
        if self._key == key and self.arrays is not None and self._rows < n \
                and append_host_fn is not None:
            self._append(mesh, n, cap, append_host_fn)
        elif self._key != key or self._rows < n or self.arrays is None:
            hosts = build_host_fn(cap)
            self.arrays = tuple(shard_rows(mesh, h)[0] for h in hosts)
            if self.sqnorm_of is not None:
                from vearch_tpu.ops.distance import host_sqnorms

                self.sqnorm = shard_rows(
                    mesh, host_sqnorms(self._logical(hosts[self.sqnorm_of]))
                )[0]
            self._key = key
            self._rows = n
            rebuilt = True
            self.stats["rebuilds"] += 1
            moved = sum(np.asarray(h).nbytes for h in hosts)
            self.stats["h2d_bytes"] += moved
            perf_model.note_h2d_bytes(moved)
        return self.arrays, rebuilt

    def _logical(self, host: np.ndarray) -> np.ndarray:
        """The `[rows, d]` view of a 2-D host array handed in packed."""
        return host.reshape(host.shape[0] * self.pack, -1)

    def _append(self, mesh: Mesh, n: int, cap: int, append_host_fn) -> None:
        """Tail-append rows [rows_hw, n) in place: the host window is
        align-rounded so every per-shard slice keeps lane-aligned static
        shapes (bounded retrace), sliced per shard, H2D'd to exactly the
        devices whose slab the window touches, and written with a
        non-donating dynamic_update_slice. Untouched shards keep their
        existing buffers — zero copies, zero traffic."""
        n_shards = mesh.shape["data"]
        local_n = cap // n_shards
        pack = self.pack
        lo = (self._rows // self.align) * self.align
        hi = min(-(-n // self.align) * self.align, cap)
        tails = [np.asarray(t) for t in append_host_fn(lo, hi)]
        sq_tail = None
        if self.sqnorm_of is not None:
            from vearch_tpu.ops.distance import host_sqnorms

            sq_tail = host_sqnorms(self._logical(tails[self.sqnorm_of]))
        new_arrays = []
        new_sq = self.sqnorm
        for ai, arr in enumerate(self.arrays):
            want_sq = self.sqnorm_of == ai
            upd = _tail_update_fn(arr.ndim, want_sq, pack)
            parts = {}
            sq_parts = {}
            for sh in arr.addressable_shards:
                s = (sh.index[0].start or 0) * pack // local_n
                a = max(lo, s * local_n)
                b = min(hi, (s + 1) * local_n)
                if a >= b:
                    parts[s] = sh.data
                    continue
                win = tails[ai][(a - lo) // pack : (b - lo) // pack]
                win_dev = jax.device_put(win, sh.device)
                self.stats["h2d_bytes"] += win.nbytes
                perf_model.note_h2d_bytes(win.nbytes)
                off = np.int32((a - s * local_n) // pack)
                if want_sq:
                    sq_sh = {
                        (q.index[0].start or 0) // local_n: q
                        for q in new_sq.addressable_shards
                    }[s]
                    sq_win = jax.device_put(
                        sq_tail[a - lo : b - lo], sh.device
                    )
                    self.stats["h2d_bytes"] += sq_win.nbytes
                    perf_model.note_h2d_bytes(sq_win.nbytes)
                    parts[s], sq_parts[s] = upd(
                        sh.data, win_dev, off, sq_sh.data, sq_win
                    )
                else:
                    parts[s] = upd(sh.data, win_dev, off)
            new_arrays.append(jax.make_array_from_single_device_arrays(
                arr.shape, arr.sharding,
                [parts[s] for s in sorted(parts)],
            ))
            if want_sq:
                sq_all = {
                    (q.index[0].start or 0) // local_n: q.data
                    for q in new_sq.addressable_shards
                }
                sq_all.update(sq_parts)
                new_sq = jax.make_array_from_single_device_arrays(
                    new_sq.shape, new_sq.sharding,
                    [sq_all[s] for s in sorted(sq_all)],
                )
        # publish by reference swap: readers see either the old or the
        # new tuple, both internally consistent
        self.arrays = tuple(new_arrays)
        self.sqnorm = new_sq
        self._rows = n
        self.stats["appends"] += 1

    def lower_rows(self, start: int) -> None:
        self._rows = min(self._rows, start)

    def invalidate(self) -> None:
        self._key = None
        self._rows = 0
        self.arrays = None
        self.sqnorm = None
