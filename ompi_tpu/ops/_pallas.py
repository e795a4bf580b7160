"""``jax.experimental.pallas`` and its TPU half, as every kernel here
imports them: ``from ompi_tpu.ops._pallas import pl, pltpu``.

The import costs most of a second and happens where the first kernel is
traced, inside some program's set-up; python makes it once a process, here,
under the host span ``import.pallas`` (``core/scopes.py``), so that the
record says which program paid for it.

``pallas_call`` here is ``pl.pallas_call`` for every kernel of the package:
calling what it returns traces the kernel's body, and that call is a
``trace.kernel`` span of the same record under the kernel's name.
"""

from ompi_tpu.core.scopes import host

with host("import.pallas"):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

__all__ = ["pl", "pltpu", "pallas_call"]


def pallas_call(kernel, *, name: str, **options):
    """``pl.pallas_call(kernel, name=name, **options)``."""
    call = pl.pallas_call(kernel, name=name, **options)

    def traced(*operands):
        with host("trace.kernel", program=name):
            return call(*operands)

    return traced
