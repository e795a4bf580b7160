"""Median milliseconds a step waited for ``next(stream)`` of
``models.data.train_stream``.  Above zero, the host sets the pace."""


def read(run):
    wait = run.median("data.next")
    return None if wait is None else wait * 1e3
