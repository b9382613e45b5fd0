"""95th percentile of request latency on the client's clock, over all
the window's requests (open loop: from when each was due)."""

from benchmark import stats


def read(obs):
    return stats.percentile(obs.lat_ms, 95) if obs.lat_ms.size else None
