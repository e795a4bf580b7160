"""The benchmark: the yardstick later PRs are measured with and may not edit.

``run.py`` runs one cell of ``BENCHMARK.json`` once.  Everything that belongs
to one configuration, model family, traffic mix, kind of job or per-layer
metric is a file of its own under ``configs/``, ``reference/``, ``traffic/``,
``runners/`` and ``metrics/``, found by the name ``BENCHMARK.json`` or the
configuration gives it; ``readers/`` holds the readers that many metrics
share, each metric giving its keys in a data file; ``lib/`` holds the
arithmetic (peaks, operation and byte counts, the trace and scope
reductions).  A ``reference/<name>.py`` is the plain model of one family: it
decides ``correct`` and says, in ``counts``, what the cost functions may
count of its parameters.
"""
