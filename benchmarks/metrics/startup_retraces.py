"""Traces of one of the package's own programs beyond the first of each
distinct program object (``ompi_tpu/core/scopes.startup()["retraces"]``,
counted where the jitted function's python body runs): above zero, a
warm-up traced, lowered and compiled a program twice."""


def read(run):
    from ompi_tpu.core import scopes

    startup = getattr(scopes, "startup", None)  # a program without the record
    if startup is None:
        return None
    return startup()["retraces"]
