"""Scheduler (engine/batching.py and the engine's shape buckets): real
query rows over the rows the program ran, padding included, summed over
the kernel.* spans that started inside the device trace; a co-batched
dispatch counts once."""

from benchmark import spans


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.bucket_fill_pct()
