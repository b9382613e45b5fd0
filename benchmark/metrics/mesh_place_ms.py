"""Index and device programs, on a mesh: a request's `mesh.place` spans
(index/ivf.py `_search_mesh`: from the index's entry to the launch:
`flush_sharded`, the sharded mask, `shard_queries`' placement over the
devices, `device_buffer_sharded`), mean per request: the counterpart of
`to_device_mask` on one chip. The span's `bytes` tag (what was uploaded
meanwhile) is logged with the spans, not read here. A request with no
such span (one chip) reads nothing."""

from benchmark import spans


def place_ms(q) -> float | None:
    found = [s for s in q.spans if s.name == "mesh.place"]
    if not found:
        return None
    return sum(s.t1_ns - s.t0_ns for s in found) / 1e6


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.mean(place_ms)
