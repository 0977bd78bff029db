"""A throwaway per-layer metric: shows that one is added as a file."""


def read(run):
    return float(len(run["job"]["step_ends"])) or None
