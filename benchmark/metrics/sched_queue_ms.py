"""Scheduler (engine/batching.py): phases.queue, the wait behind the
dispatch in flight, mean per request."""

from benchmark import stats


def read(obs):
    v = obs.prof("ps_queue_ms")
    return stats.finite_mean(v)
