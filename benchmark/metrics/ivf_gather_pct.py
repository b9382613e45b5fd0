"""Kernels, IVF probe regime: of the serving program's device time
inside the traced window, the share spent in gathers: operations that
are a `gather` or a `dynamic-slice` by their own name, and the fusions
the chip's compiler makes of a gather.

The second rule is there because on the chip no gather of
`ivfflat_candidates` keeps its name: each is wrapped into a fusion of
`kind=kCustom` under the bare name `%fusion.N` (the compiler's
descriptive names, `%multiply_reduce_fusion.4`, `%pad_add_fusion.2`,
are for its loop fusions). In the program as PR 32 traced it those are
exactly its five gathers: the step's [B, tile, d] rows out of the
bucket table, their ids and squared norms, the validity mask looked up
slot by slot (`valid[ids]`: [B * tile] single elements), and the
fold's `take_along_axis` of the ids its sort kept. The loop that holds
them (`while`; `call`, `conditional`) spans its whole body and is no
operation of its own. The product, the masks' arithmetic and the sorts
are the rest of the module's time (`ivf_fold_topk_pct` reads the
sorts). A program that is not on the trace under the kernel's module
name reads nothing.
"""

import bisect
import re

from benchmark import cells, trace

CONTROL_FLOW = ("while", "call", "conditional")


def head(op_name: str) -> str:
    """The operation's own name: `%gather.3` of `%gather.3 = ...`."""
    return op_name.split(" = ", 1)[0]


def opcode(op_name: str) -> str:
    """`fusion` of `%fusion.2 = f32[8]{0} fusion(...)`, `sort` of
    `%sort.8 = (f32[8]{0}, s32[8]{0}) sort(...)`; "" if the name is not
    an instruction's text."""
    m = re.match(r"\S+ = (?:\(.*?\)|\S+) ([\w\-]+)\(", op_name)
    return m.group(1) if m else ""


def is_gather(op_name: str) -> bool:
    own = head(op_name)
    if "gather" in own or "dynamic-slice" in own or "dynamic_slice" in own:
        return True
    return (re.fullmatch(r"%?fusion(\.\d+)?", own) is not None
            and "kind=kCustom" in op_name)


def module_ops(obs):
    """Per chip: (the serving program's events in the window, the
    operations that started inside one of them, control flow left out)."""
    serving = cells.kernel(obs.config["serving"]["kernel"]).MODULE_SUBSTRING
    lo, hi = obs.trace_lo_ns, obs.trace_hi_ns
    for plane in trace.device_planes(obs.trace):
        mods = sorted((e[1], e[1] + e[2])
                      for e in trace._line(plane, trace.MODULES_LINE)
                      if serving in e[0] and lo <= e[1] < hi)
        if not mods:
            continue
        starts = [m[0] for m in mods]
        ops = []
        for name, start, dur in trace._line(plane, trace.OPS_LINE):
            i = bisect.bisect_right(starts, start) - 1
            if (i >= 0 and start < mods[i][1]
                    and opcode(name) not in CONTROL_FLOW):
                ops.append((name, dur))
        yield mods, ops


def share_pct(obs, counts) -> float | None:
    """Mean over chips of (time of the module's operations that `counts`
    holds) / the module's time."""
    if obs.trace is None:
        return None
    shares = []
    for mods, ops in module_ops(obs):
        spent = sum(dur for name, dur in ops if counts(name))
        shares.append(spent / sum(b - a for a, b in mods))
    return 100.0 * sum(shares) / len(shares) if shares else None


def read(obs):
    return share_pct(obs, is_gather)
