"""Seconds the program's own record (``ompi_tpu/core/scopes.startup()``)
puts under its ``build.*`` and ``import.*`` host spans, as self time: the
bodies of the factories the cell called (``make_train_step``,
``make_decoder``, ``train_stream``) and the package's first imports of
pallas and of jax, less the stages of any program compiled inside them.
The whole process's, which on the chip is one run; ``import jax`` made by
``run.py`` itself is in no span."""


def read(run):
    from ompi_tpu.core import scopes

    startup = getattr(scopes, "startup", None)  # a program without the record
    if startup is None:
        return None
    return sum(seconds for name, seconds in startup()["spans"].items()
               if name.startswith(("build.", "import.")))
