"""Index and device programs, IVF probe regime: of the slots of the
published bucket table (`nlist` lists of `cap` slots each: the longest
list sets `cap` for all), the share that holds a row. A probe scans
whole lists, padding included, so this is the share of the scan's
gathered rows, products and folded scores that could matter. Read from
the `fill` tag of a request's `ivf.probe` span (index/ivf.py: the probe
phase, from the index's entry to the launch; its `nprobe` and `cap`
tags are logged with the spans), mean over the window's requests. A
request with no such span (a full-scan configuration, or a program
from before the span) reads nothing."""

from benchmark import spans


def fill_pct(q) -> float | None:
    found = [s.tags["fill"] for s in q.spans
             if s.name == "ivf.probe" and "fill" in s.tags]
    if not found:
        return None
    return 100.0 * sum(found) / len(found)


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.mean(fill_pct)
