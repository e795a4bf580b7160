"""Cell 2's programs compile for the chip (``fits_case.py`` has the body)."""

from tests.benchmarks.fits_case import (case, chips,  # noqa: F401
                                        no_compile_cache)

CELL = "pythia-1.4b-widths.decode-1k-128"
test_cell_programs_compile_for_the_chip = case(CELL)
