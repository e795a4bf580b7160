"""What one run hands to the per-layer metrics' readers."""

from __future__ import annotations

import dataclasses
import statistics

from benchmarks.lib.xplane import Event, TraceSummary


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
    # ``lib/scopes.reduce_scopes`` of the traced samples: device seconds by
    # pass, named scope and collective site.  None without a device trace.
    scopes: dict[str, float] | None = None
    # the traced events that table was made of, for ``scopes_under``
    events: list[Event] = dataclasses.field(default_factory=list, repr=False)
    # the cell that was run: its configuration's file and its traffic
    # mix's, as the runner was given them
    config: dict = dataclasses.field(default_factory=dict, repr=False)
    traffic: dict = dataclasses.field(default_factory=dict, repr=False)

    def median(self, span: str) -> float | None:
        values = self.durations.get(span)
        return statistics.median(values) if values else None

    def scopes_under(self, span: str) -> dict[str, float] | None:
        """The scope table of the program runs under one host span of the
        benchmark alone: a sample may run several programs whose scopes
        have the same names."""
        from benchmarks.lib import scopes   # a traced run's, not set-up's

        return scopes.reduce_scopes(self.events, span=span)
