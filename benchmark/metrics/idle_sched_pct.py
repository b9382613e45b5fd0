"""Device idle time, put down to a layer: the share of the traced window in
which the chip was idle while the request that ended the gap was in the
scheduler (microbatch.queue, batch.pack). The five idle_*_pct add up to
device_idle_pct (benchmark/spans.py idle_ms_by_layer)."""

from benchmark import spans


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.idle_pct("sched")
