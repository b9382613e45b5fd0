"""Index and device programs: `launch_us` of a request's kernel.* spans
(until the jitted call returned: program enqueued, query uploaded), mean
per request."""

from benchmark import spans


def read(obs):
    a = spans.of(obs)
    if a is None:
        return None
    return a.mean(a.launch_ms)
