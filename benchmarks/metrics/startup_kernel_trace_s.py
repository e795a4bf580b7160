"""Seconds of the package's own programs' traces that went to their pallas
kernels: self time of the host span ``trace.kernel`` (around every
``pallas_call``, ``ompi_tpu/ops/_pallas.py``: the kernel's body traced)
inside an own program, from the program's own record
(``ompi_tpu/core/scopes.startup()["trace"]``).  0 where the cell's programs
call no kernel; part of ``startup_trace_s``."""


def read(run):
    from ompi_tpu.core import scopes

    startup = getattr(scopes, "startup", None)  # a program without the record
    trace = startup().get("trace") if startup else None
    if trace is None:                           # ... or without its split
        return None
    return sum(row["own_s"] for row in trace.get("trace.kernel", {}).values())
