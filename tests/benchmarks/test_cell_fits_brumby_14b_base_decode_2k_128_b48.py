"""Cell 8's programs compile for the chip (``fits_case.py`` has the body)."""

from tests.benchmarks.fits_case import (case, chips,  # noqa: F401
                                        no_compile_cache)

CELL = "brumby-14b-base.decode-2k-128-b48"
test_cell_programs_compile_for_the_chip = case(CELL)
