"""Device: 1 - (union of device-op intervals / traced window)."""

from benchmark import trace


def read(obs):
    if obs.trace is None:
        return None
    busy = trace.busy_seconds(obs.trace, obs.trace_lo_ns, obs.trace_hi_ns)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ((obs.trace_hi_ns - obs.trace_lo_ns) / 1e9))
