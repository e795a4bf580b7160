"""Milliseconds the host spends inside one call of the train step's
callable, from the program's own record
(``ompi_tpu/core/scopes.run()["callables"]``, a row a callable in the order
they were made; the first of the name that has run): the median ``run.call`` span, entry
to the return of its dispatch, of the calls in which nothing went to the
backend.  The program's own part of what the benchmark times from outside as
``dispatch``."""


def read(run):
    from ompi_tpu.core import scopes

    record = getattr(scopes, "run", None)   # a program without the run half
    rows = [row for row in record()["callables"]
            if row["program"] == "train_step"
            and row["median_s"] is not None] if record else []
    return rows[0]["median_s"] * 1e3 if rows else None
