"""Index and device programs (index/ivf.py, ops/ivf.py): the sum of
dispatches.per_dispatch_ms as the host observed them, mean per request."""

from benchmark import stats


def read(obs):
    v = obs.prof("dispatch_sum_ms")
    return stats.finite_mean(v)
