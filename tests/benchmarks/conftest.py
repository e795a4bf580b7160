"""The ``test_cell_fits_<cell>.py`` files (``fits_case.py``; on demand, ``-m
slow``) compile every cell's programs for a described v5e with the chip's own
compiler.  The suite's session fixture (``tests/conftest.py``) puts pallas
kernels into TPU interpret mode, for the CPU; under it a program with a
kernel in it would compile host callbacks that are sent the kernel's whole
operands, not the kernel.  Those compiles get the bare behaviour."""

import pytest


@pytest.fixture(autouse=True)
def _kernels_compile_for_the_chip_where_the_chip_is_compiled_for(request):
    if not request.module.__name__.rpartition(".")[2].startswith(
            "test_cell_fits_"):
        yield
        return
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode(None):
        yield
