"""Shared reader: device milliseconds under the scopes a metric names, in
one run of the program that a host span of the benchmark encloses.

``metrics/<metric>.json`` gives ``{"reader": "scope_ms_per_run", "span":
"first", "keys": ["scope/prefill"]}``.  A sample may run several programs
whose scopes have the same names (the decode job's two each have a
``prefill``): the table is made of the program runs that lie mostly under
the host span ``span`` alone, and divided by their number."""


def read(run, spec):
    from benchmarks.lib import scopes   # a traced run's, not set-up's

    if run.scopes is None:      # no device trace in this run
        return None
    table = run.scopes_under(spec["span"])
    took = scopes.seconds(table, spec["keys"])
    if took is None or not table["executions"]:
        scopes.warn_missing(spec["name"], spec["keys"],
                            where=f" in the runs under the host span "
                                  f"{spec['span']!r}")
        return None
    return 1e3 * took / table["executions"]
