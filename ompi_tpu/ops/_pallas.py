"""``jax.experimental.pallas`` and its TPU half, as every kernel here
imports them: ``from ompi_tpu.ops._pallas import pl, pltpu``.

The import costs most of a second and happens where the first kernel is
traced, inside some program's set-up; python makes it once a process, here,
under the host span ``import.pallas`` (``core/scopes.py``), so that the
record says which program paid for it.
"""

from ompi_tpu.core.scopes import host

with host("import.pallas"):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

__all__ = ["pl", "pltpu"]
