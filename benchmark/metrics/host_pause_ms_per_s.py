"""Partition server's process: total duration of proc.gc spans (garbage
collections of generation 2, and any over a millisecond) inside the
window, per second of it."""

from benchmark import spans


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.gc_ms / obs.seconds
