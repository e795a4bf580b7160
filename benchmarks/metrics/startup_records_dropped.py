"""Spans the program's own record did not keep because it was full
(``ompi_tpu/core/scopes.startup()["dropped"]``, those that ended after the
``LIMIT``-th): 0 in a sound run.  Above it a stage's record may be missing
and the other ``startup_*`` readings are short of what the process spent."""


def read(run):
    from ompi_tpu.core import scopes

    startup = getattr(scopes, "startup", None)  # a program without the record
    out = startup() if startup else {}
    if "calls" not in out:      # the parent's: counted, and folded nowhere
        return None
    return out["dropped"]
