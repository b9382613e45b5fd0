"""Tracing: the clients' median latency in the traced run (profile: true
on every request, a profiler trace taken inside the window). Beside
search_p50_ms of the untraced runs it is what tracing costs when on."""

from benchmark import stats


def read(obs):
    if obs.trace is None or not obs.lat_ms.size:
        return None
    return stats.percentile(obs.lat_ms, 50)
