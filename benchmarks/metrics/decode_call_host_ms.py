"""Milliseconds the host spends inside one call of the first decoder the
process built that has run (the runner's ``first``: a prompt and one token),
from the program's own record (``ompi_tpu/core/scopes.run()["callables"]``,
a row a callable in the order they were made): the median ``run.call`` span,
entry to the return of its last dispatch, of that callable's calls in which
nothing went to the backend.  One callable and not the two together: a
``full`` call of a plan's decoder may hold the host for the whole prefill
(cell 10), and a median over both kinds follows their counts.  The
program's own part of what the benchmark times from outside as
``dispatch``."""


def read(run):
    from ompi_tpu.core import scopes

    record = getattr(scopes, "run", None)   # a program without the run half
    rows = [row for row in record()["callables"]
            if row["program"] == "decode"
            and row["median_s"] is not None] if record else []
    return rows[0]["median_s"] * 1e3 if rows else None
