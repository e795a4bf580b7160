"""Seconds the package's own program objects spent being lowered, a jaxpr to
an MLIR module (the kernels' bodies to Mosaic's inside it), from the
program's own record (``ompi_tpu/core/scopes.startup()["calls"]``, a row an
object, JAX's clock, self time).  With ``startup_trace_s`` it adds up to
``startup_trace_lower_s``."""


def read(run):
    from ompi_tpu.core import scopes

    startup = getattr(scopes, "startup", None)  # a program without the record
    calls = startup().get("calls") if startup else None
    if calls is None:                           # ... or without its split
        return None
    return sum(row["lower_s"] for row in calls)
