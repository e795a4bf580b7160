"""Seconds the package's own programs (``train_step``, ``decode``: those a
factory registered) spent being traced and lowered, from the program's own
record (``ompi_tpu/core/scopes.startup()``, JAX's clock by program name,
self time): python work that no compilation cache saves.  The helpers
traced inside such a program are its seconds; a lazy import inside its
trace is not (``startup_build_s``)."""


def read(run):
    from ompi_tpu.core import scopes

    startup = getattr(scopes, "startup", None)  # a program without the record
    if startup is None:
        return None
    return sum(row["trace_s"] + row["lower_s"]
               for row in startup()["programs"].values())
