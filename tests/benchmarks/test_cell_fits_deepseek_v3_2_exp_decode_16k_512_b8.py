"""Cell 14's programs compile for the chip (``fits_case.py`` has the body)."""

from tests.benchmarks.fits_case import (case, chips,  # noqa: F401
                                        no_compile_cache)

CELL = "deepseek-v3.2-exp.decode-16k-512-b8"
test_cell_programs_compile_for_the_chip = case(CELL)
