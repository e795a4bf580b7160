"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window, in GiB.  A ceiling to watch, not a cost: a change may spend memory
for speed, and a cell that no longer fits fails by itself."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 2 ** 30
