"""Seconds of trace, lowering and backend of every program of the process
that is not the package's own, from the program's own record
(``ompi_tpu/core/scopes.startup()``): the benchmark's parameter draws, the
optimizer state's ``zeros_like``, helper jits, and the checker's plain
reference.  What a set-up pays that is not the program's.  The reference's
programs run under the runners' span ``setup.reference``, which ``setup_s``
leaves out, so this reads more than ``setup_s`` holds of them."""


def read(run):
    from ompi_tpu.core import scopes

    startup = getattr(scopes, "startup", None)  # a program without the record
    if startup is None:
        return None
    return sum(row["trace_s"] + row["lower_s"] + row["backend_s"]
               for row in startup()["others"].values())
