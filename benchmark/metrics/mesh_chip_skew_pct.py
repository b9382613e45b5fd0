"""Device, on a mesh: (busiest chip - idlest chip) over the mean of the
chips' busy time in the traced window (busy as device_idle_pct has it:
the union of a chip's operation intervals): whether the shards are
even. One chip, or no device plane, reads nothing."""

from benchmark import trace


def read(obs):
    if obs.trace is None:
        return None
    busy = [trace.union_ns(trace.clip(trace.op_events(p), obs.trace_lo_ns,
                                      obs.trace_hi_ns))
            for p in trace.device_planes(obs.trace)]
    if len(busy) < 2 or sum(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
