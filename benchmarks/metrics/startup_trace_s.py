"""Seconds the package's own program objects spent being traced, from the
program's own record (``ompi_tpu/core/scopes.startup()["calls"]``, a row an
object, JAX's clock, self time): the python of the jitted functions, their
layers (``trace.layer``) and kernel bodies (``trace.kernel``) and the helper
traces folded into them.  With ``startup_lower_s`` it adds up to
``startup_trace_lower_s``."""


def read(run):
    from ompi_tpu.core import scopes

    startup = getattr(scopes, "startup", None)  # a program without the record
    calls = startup().get("calls") if startup else None
    if calls is None:                           # ... or without its split
        return None
    return sum(row["trace_s"] for row in calls)
