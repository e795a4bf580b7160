"""The benchmark: the yardstick later PRs are measured with and may not edit.

``run.py`` runs one cell of ``BENCHMARK.json`` once.  Everything that belongs
to one configuration, traffic mix, kind of job or per-layer metric is a file
of its own under ``configs/``, ``traffic/``, ``runners/`` and ``metrics/``,
found by the name ``BENCHMARK.json`` gives it; ``lib/`` holds the arithmetic
(peaks, operation and byte counts, the trace reduction) and ``reference/`` the
plain model that decides ``correct``.
"""
