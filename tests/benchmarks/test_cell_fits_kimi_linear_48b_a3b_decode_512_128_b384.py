"""Cell 7's programs compile for the chip (``fits_case.py`` has the body)."""

from tests.benchmarks.fits_case import (case, chips,  # noqa: F401
                                        no_compile_cache)

CELL = "kimi-linear-48b-a3b.decode-512-128-b384"
test_cell_programs_compile_for_the_chip = case(CELL)
