"""Index and device programs, three-stage refinement: a request's
`refine.place` spans (index/binary.py `IVFRaBitQIndex.search`: from the
index's entry to the launch: the bit planes', the int8 rows' and the
raw store's device placement or tail append, the validity mask, the
query upload), mean per request: the counterpart of `mesh.place` and
`ivf.probe`. The span's `r0`, `r1`, `rows`, `plane_bytes` and
`mirror_bytes` tags are logged with the spans, not read here. A request
with no such span (another index, or a program from before the span)
reads nothing."""

from benchmark import spans


def place_ms(q) -> float | None:
    found = [s for s in q.spans if s.name == "refine.place"]
    if not found:
        return None
    return sum(s.t1_ns - s.t0_ns for s in found) / 1e6


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.mean(place_ms)
