"""Median latency of a request on the client's clock (open loop: from
when the request was due)."""

from benchmark import stats


def read(obs):
    return stats.percentile(obs.lat_ms, 50) if obs.lat_ms.size else None
