"""Device: memory_stats()["peak_bytes_in_use"] after the window, on the
fullest chip. The build runs in a child process, so this is serving's."""


def read(obs):
    return obs.memory_peak_bytes / 1e9 if obs.memory_peak_bytes else None
