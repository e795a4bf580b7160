"""Share of the traced window in which no operation ran and no collective
was in flight on a device, mean over the devices."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
