"""Milliseconds of one cached decode step for the whole batch: the median
``full`` job minus the median ``first`` job, over ``max_new - 1`` steps."""


def read(run):
    first, full = run.median("first"), run.median("full")
    if first is None or full is None:
        return None
    return (full - first) / (run.facts["max_new"] - 1) * 1e3
