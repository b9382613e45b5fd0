"""Process start to the window's opening: corpus build or cache hit,
restore, first placement, warm-up, generator start."""


def read(obs):
    return obs.setup_s
