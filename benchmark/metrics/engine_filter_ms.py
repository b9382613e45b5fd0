"""Engine (engine/engine.py): a request's `engine.filter` spans (the
filter evaluated on the host into an [n] bool mask, ANDed with the alive
mask; where there is no filter, the device-resident alive mask looked
up), mean per request. A request without the span reads nothing."""

from benchmark import spans


def filter_ms(q) -> float | None:
    found = [s for s in q.spans if s.name == "engine.filter"]
    if not found:
        return None
    return sum(s.t1_ns - s.t0_ns for s in found) / 1e6


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.mean(filter_ms)
