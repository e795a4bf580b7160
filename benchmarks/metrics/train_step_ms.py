"""Median milliseconds of one blocked optimizer step: dispatch of the
``make_train_step`` program to the loss being ready, the input wait
excluded."""


def read(run):
    step = run.median("step")
    return None if step is None else step * 1e3
