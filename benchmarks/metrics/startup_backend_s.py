"""Seconds the package's own programs (``train_step``, ``decode``) spent at
the backend, from the program's own record (``ompi_tpu/core/scopes.
startup()``, JAX's clock by program name): a compile where the persistent
cache missed, the read of the executable where it hit."""


def read(run):
    from ompi_tpu.core import scopes

    startup = getattr(scopes, "startup", None)  # a program without the record
    if startup is None:
        return None
    return sum(row["backend_s"] for row in startup()["programs"].values())
