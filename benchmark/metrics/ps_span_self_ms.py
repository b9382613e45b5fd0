"""Partition server (cluster/rpc.py, cluster/ps.py), from the program's
spans: self time of the PS's rpc.serve, rpc.decode, rpc.encode,
ps.search, ps.pre, ps.gate_wait and ps.post, mean per request. The
scheduler's, engine's and kernel spans are children and are out."""

from benchmark import spans


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.mean(lambda q: q.self_ms("ps", spans.PS_SELF))
