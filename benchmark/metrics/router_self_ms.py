"""Router layer (sdk/client.py, cluster/rpc.py, cluster/router.py):
client wall time minus the slowest partition's rpc_ms, mean per request."""

from benchmark import stats


def read(obs):
    wall = (obs.win["t_done"] - obs.win["t_send"]) * 1e3
    v = wall - obs.prof("rpc_ms")
    return stats.finite_mean(v)
