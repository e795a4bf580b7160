"""Seconds of the package's own program objects' first dispatches that no
stage of JAX's accounts for, from the program's own record
(``ompi_tpu/core/scopes.run()["programs"]``, a row an object): a first
``run.dispatch`` less the ``compile.*``, ``trace.*`` and ``import.*`` spans
inside it, summed over the objects.  What lies between the backend's return
and the first call's: loading the executable, laying out the arguments,
the dispatch itself."""


def read(run):
    from ompi_tpu.core import scopes

    record = getattr(scopes, "run", None)   # a program without the run half
    if record is None:
        return None
    rests = [row["first_rest_s"] for row in record()["programs"]
             if row["first_rest_s"] is not None]
    return sum(rests) if rests else None
