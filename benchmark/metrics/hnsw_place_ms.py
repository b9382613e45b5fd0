"""Index and device programs, HNSW's device scan: a request's
`hnsw.place` spans (index/hnsw.py `HNSWIndex.search`: from the index's
entry to the launch: the tail absorb, the int8 rows' flush, the raw
store's placement as super-rows or its tail append, the validity mask,
the query's normalisation and upload), mean per request: the
counterpart of `refine.place` and `mesh.place`. The span's `ef`, `rows`,
`mirror_bytes` and `store_pack` tags are logged with the spans, not read
here. A request with no such span (another index, or a program from
before the span) reads nothing."""

from benchmark import spans


def place_ms(q) -> float | None:
    found = [s for s in q.spans if s.name == "hnsw.place"]
    if not found:
        return None
    return sum(s.t1_ns - s.t0_ns for s in found) / 1e6


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.mean(place_ms)
