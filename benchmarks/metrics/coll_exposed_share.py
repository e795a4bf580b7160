"""Share of the traced window in which a collective was in flight on a
device and no other operation ran there: what a faster or better hidden
collective could give back, at most."""


def read(run):
    t = run.trace
    return None if t is None else 100.0 * t.exposed_collective_s / t.window_s
