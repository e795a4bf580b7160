"""Share of the traced window in which a collective (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) was in flight on a device,
mean over the devices; an asynchronous pair counts once."""


def read(run):
    t = run.trace
    return None if t is None else 100.0 * t.collective_s / t.window_s
