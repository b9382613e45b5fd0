"""Device idle time, put down to a layer: the share of the traced window in
which the chip was idle while the request that ended the gap was in the
partition server's handler (rpc.*, ps.*) or on the hop to it
(router.scatter outside any PS span). The five idle_*_pct add up to
device_idle_pct (benchmark/spans.py idle_ms_by_layer)."""

from benchmark import spans


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.idle_pct("ps")
