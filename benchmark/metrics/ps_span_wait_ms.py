"""Partition server: over the PS's leaves that run on the handler thread
(rpc.decode, ps.pre, ps.post, rpc.encode), wall time minus the thread's
CPU time, summed per request, mean per request: the thread had work and
was not running (the interpreter lock, a lock, the socket)."""

from benchmark import spans


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.mean(lambda q: q.wait_ms())
