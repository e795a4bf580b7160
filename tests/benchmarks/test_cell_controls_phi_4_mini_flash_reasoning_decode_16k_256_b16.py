"""Cell 15's cases of the controls' tests (``controls_cases.py`` has them)."""

from tests.benchmarks import controls_cases

CELL = "phi-4-mini-flash-reasoning.decode-16k-256-b16"
globals().update(controls_cases.tests_of([CELL]))
