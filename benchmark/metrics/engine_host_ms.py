"""Engine (engine/engine.py): phases.total (stamped after the scheduler's
queue) minus the host-observed dispatches, mean per request: filter,
merge and result shaping."""

from benchmark import stats


def read(obs):
    v = obs.prof("ps_total_ms") - obs.prof("dispatch_sum_ms")
    return stats.finite_mean(v)
