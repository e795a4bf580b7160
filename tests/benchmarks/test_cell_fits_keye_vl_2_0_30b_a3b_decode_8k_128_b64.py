"""Cell 6's programs compile for the chip (``fits_case.py`` has the body)."""

from tests.benchmarks.fits_case import (case, chips,  # noqa: F401
                                        no_compile_cache)

CELL = "keye-vl-2.0-30b-a3b.decode-8k-128-b64"
test_cell_programs_compile_for_the_chip = case(CELL)
