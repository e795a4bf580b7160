"""Cell 12's programs compile for the chip (``fits_case.py`` has the body)."""

from tests.benchmarks.fits_case import (case, chips,  # noqa: F401
                                        no_compile_cache)

CELL = "nemotron-3-nano-30b-a3b.decode-1k-128-b256"
test_cell_programs_compile_for_the_chip = case(CELL)
