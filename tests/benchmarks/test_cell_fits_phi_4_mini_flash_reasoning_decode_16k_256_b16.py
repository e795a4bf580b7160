"""Cell 15's programs compile for the chip (``fits_case.py`` has the body)."""

from tests.benchmarks.fits_case import (case, chips,  # noqa: F401
                                        no_compile_cache)

CELL = "phi-4-mini-flash-reasoning.decode-16k-256-b16"
test_cell_programs_compile_for_the_chip = case(CELL)
