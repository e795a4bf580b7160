"""Shared reader: percent of the traced window in which the device ran
under the scopes a metric names.

``metrics/<metric>.json`` gives ``{"reader": "scope_share", "keys": [...]}``:
keys of the scope table of ``lib/scopes.py`` (``fnmatch`` patterns:
``phase/bwd``, ``scope/attention@layers``, ``coll/*.tp``), whose seconds are
summed.  The table is a union of intervals a key, so keys that overlap in
time (``scope/layers`` and ``scope/attention``) must not share a metric.  A
metric none of whose keys the table has is left out, and said on stderr."""


def read(run, spec):
    from benchmarks.lib import scopes   # a traced run's, not set-up's

    if run.scopes is None:      # no device trace in this run
        return None
    took = scopes.seconds(run.scopes, spec["keys"])
    if took is None:
        scopes.warn_missing(spec["name"], spec["keys"])
        return None
    return 100.0 * took / run.trace.window_s
