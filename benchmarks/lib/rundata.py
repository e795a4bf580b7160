"""What one run hands to the per-layer metrics' readers."""

from __future__ import annotations

import dataclasses
import statistics

from benchmarks.lib.xplane import TraceSummary


@dataclasses.dataclass
class RunData:
    """A reader takes what it needs and returns ``None`` where that is
    missing (no such span in this job, no trace in this run, no peaks for
    this device); the harness then leaves its metric out of the line."""
    durations: dict[str, list[float]]   # seconds of the window's host spans
    facts: dict                         # the job's sizes and costs
    peaks: dict | None                  # lib/peaks.py row of this device
    trace: TraceSummary | None          # of the traced samples, --trace 1
    compiles_in_window: int             # programs sent to the backend
    peak_bytes: int | None              # fullest chip, memory_stats()

    def median(self, span: str) -> float | None:
        values = self.durations.get(span)
        return statistics.median(values) if values else None
