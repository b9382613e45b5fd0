"""Router (cluster/rpc.py, cluster/router.py), from the program's spans:
self time of the router's rpc.serve, rpc.decode, rpc.encode,
router.search and router.merge, mean per request. router.scatter is a
child, so the RPC and the partition server are out."""

from benchmark import spans


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.mean(lambda q: q.self_ms("router", spans.ROUTER_SELF))
