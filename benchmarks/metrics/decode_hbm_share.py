"""Share of the published HBM bandwidth a decode step reaches, counting only
the bytes it must read (``lib/costs.decode_step_bytes``: the parameters a
step reads, at the type it reads them in, and the live keys and values,
once).  The cached step is bound by memory, not by operations: this is that
bound's share, for the step as a whole and not for one kernel."""


def read(run):
    first, full = run.median("first"), run.median("full")
    if first is None or full is None or run.peaks is None:
        return None
    step_s = (full - first) / (run.facts["max_new"] - 1)
    return (100.0 * run.facts["decode_step_bytes"] / step_s
            / run.peaks["hbm_bytes_per_s"])
