"""Kernels, on a mesh: of the serving program's device time inside the
traced window, the share spent in collective operations (`all-gather`,
`all-reduce`, `collective-permute` in the operation's own name, its
`-start` / `-done` halves included), per chip, mean over the chips.
That is ICI transfer plus each chip's wait for the slowest shard: the
bytes are some 0.6 MB a dispatch, so it is latency and skew. A program
that is not on the trace under the kernel's module name (one chip, or
the parent of the PR that named it) reads nothing."""

import bisect

from benchmark import cells, trace

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute")


def is_collective(op_name: str) -> bool:
    """By the operation's own name (`%all-gather.3 = ...`), not by its
    operands: the fusion that reads a gathered array names it too."""
    head = op_name.split(" = ", 1)[0]
    return any(c in head for c in COLLECTIVES)


def read(obs):
    if obs.trace is None:
        return None
    serving = cells.kernel(obs.config["serving"]["kernel"]).MODULE_SUBSTRING
    lo, hi = obs.trace_lo_ns, obs.trace_hi_ns
    shares = []
    for plane in trace.device_planes(obs.trace):
        mods = sorted((e[1], e[1] + e[2])
                      for e in trace._line(plane, trace.MODULES_LINE)
                      if serving in e[0] and lo <= e[1] < hi)
        if not mods:
            continue
        starts = [m[0] for m in mods]
        spent = 0.0
        for name, start, dur in trace._line(plane, trace.OPS_LINE):
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < mods[i][1] and is_collective(name):
                spent += dur
        shares.append(spent / sum(b - a for a, b in mods))
    return 100.0 * sum(shares) / len(shares) if shares else None
