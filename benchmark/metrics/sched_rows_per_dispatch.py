"""Scheduler: real query rows answered in the traced window per device
dispatch of the serving program there (how well callers co-batch)."""

from benchmark import cells, trace


def read(obs):
    if obs.trace is None:
        return None
    kern = cells.kernel(obs.config["serving"]["kernel"])
    n = len(trace.program_events(obs.trace, kern.MODULE_SUBSTRING,
                                 obs.trace_lo_ns, obs.trace_hi_ns))
    if n == 0:
        return None
    return traced_rows(obs) / n


def traced_rows(obs) -> int:
    """Rows of the requests answered inside the traced window."""
    done_ns = obs.rec["t_done"] * 1e9 - obs.trace_offset_ns
    inside = ((done_ns >= obs.trace_lo_ns) & (done_ns < obs.trace_hi_ns)
              & obs.rec["ok"])
    return int(obs.rec["q_idx"][inside].size)
