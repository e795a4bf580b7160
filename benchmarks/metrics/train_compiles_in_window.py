"""Programs that went to the backend between the first and the last timed
sample.  Every sample that compiled is counted as failed."""


def read(run):
    return run.compiles_in_window
