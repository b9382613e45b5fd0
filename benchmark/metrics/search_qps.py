"""Query rows answered per second: every row of every request that
completed inside the window, over the whole window."""

from benchmark import stats


def read(obs):
    return stats.rate(obs.win["q_idx"].size, obs.seconds)
