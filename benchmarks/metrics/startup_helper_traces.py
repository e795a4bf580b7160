"""Helper stages folded into the package's own programs: every trace,
lowering or compile of a program no factory registered (``multiply``,
``_where``, a plan's layer) that began inside a stage of an own program and
so left no record of its own (``ompi_tpu/core/scopes.startup()``, the
``helpers`` of ``programs``' rows).  The size of the python a trace runs."""


def read(run):
    from ompi_tpu.core import scopes

    startup = getattr(scopes, "startup", None)  # a program without the record
    out = startup() if startup else {}
    if "calls" not in out:                      # ... or without its split
        return None
    return sum(row["helpers"] for row in out["programs"].values())
