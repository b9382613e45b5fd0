"""Mean recall@10 of the window's own answers against the plain
reference, in percent."""


def read(obs):
    return 100.0 * obs.recall
