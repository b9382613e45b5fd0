"""Kernels (ops/ivf.py int8_scan_rerank, found in the trace by its XLA
module name): the least time the chip could take for the needed work of
the traced window's dispatches, over the device time they took."""

import sys

from benchmark import cells, trace
from benchmark.metrics.sched_rows_per_dispatch import traced_rows


def read(obs):
    if obs.trace is None or obs.peak is None:
        return None
    cfg = obs.config
    kern = cells.kernel(cfg["serving"]["kernel"])
    events = trace.program_events(obs.trace, kern.MODULE_SUBSTRING,
                                  obs.trace_lo_ns, obs.trace_hi_ns)
    device_s = sum(e[2] for e in events) / 1e9
    rows = traced_rows(obs)
    if not events or device_s <= 0 or rows == 0:
        return None
    r = int(cfg["search"]["index_params"]["rerank"])
    per_row = kern.needed(1, obs.rows, cfg["dimension"], r)
    none = kern.needed(0, obs.rows, cfg["dimension"], r)
    work = {"flops": rows * per_row["flops"],
            "bytes": len(events) * none["bytes"]
            + rows * (per_row["bytes"] - none["bytes"])}
    least, bound = kern.least_seconds(work, obs.peak)
    print(f"roofline: {len(events)} dispatches, {rows} rows, device "
          f"{device_s:.4f}s, least {least:.4f}s ({bound}-bound)",
          file=sys.stderr)
    return 100.0 * least / device_s
