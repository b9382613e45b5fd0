"""Partition server (cluster/ps.py): the router's rpc_ms minus the
scheduler's queue wait and the engine's phases.total (which starts after
that wait), mean per request: HTTP decode, admission gate, reply
encoding, and the wait for the interpreter lock among handler threads."""

from benchmark import stats


def read(obs):
    v = obs.prof("rpc_ms") - obs.prof("ps_queue_ms") - obs.prof("ps_total_ms")
    return stats.finite_mean(v)
