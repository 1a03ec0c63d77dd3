"""Seconds per job step: the whole window over all the steps finished in
it, each step a hypergradient of the cell's stated accuracy."""


def read(run):
    w = run.window
    return w.seconds / w.completed if w.completed else None
