"""Share of its roofline that the cached steps' reads of a shared K/V cache
reach: the least time the chip could take to read the live K and V of the one
layer that owns them once for every layer that reads them without owning any,
over the steps of the traced ``full`` jobs, over the device time under the
scope ``attention.shared`` of the cached steps, whatever implements the read.

The reads are bound by bytes: a reading layer's queries wait for the layer
under it, so each of the ``readers`` layers (the reference's ``shared_kv``)
must pass over every live position's ``kv_elements`` at ``kv_cache_dtype``
for every sequence; the products against a row (a few query heads of 64) are
the MXU's in a fraction of the time the row takes to arrive.  The cache is
live up to the position being written: ``prompt_len + max_new / 2`` positions
on the mean over the ``max_new - 1`` steps.  The count is of shapes, from the
cell's own files; the owning layer's own read is under ``attention`` alone and
is not in it, nor in the time.  A read that passes over K for the scores and
over V for the context reads what is counted once; the share cannot pass 100%
while the reads run under that scope.

A run whose reference names no shared cache, or whose trace has no time under
the scope, reads as nothing.
"""

KEYS = ["scope/attention.shared@decode.step"]
SPAN = "full"       # the job that takes cached steps
JOBS = "first"      # the job that is one run of one program


def cost_bytes(batch: int, readers: int, kv_elements: int, positions: float,
               itemsize: int) -> float:
    """Bytes one cached step's shared reads need."""
    return readers * batch * positions * kv_elements * itemsize


def read(run):
    import jax.numpy as jnp

    from benchmarks.lib import program, scopes

    config = run.config or {}
    if run.scopes is None or run.peaks is None or "reference" not in config:
        return None
    ref = program.reference(config)
    if not hasattr(ref, "shared_kv"):
        return None
    took = scopes.seconds(run.scopes_under(SPAN), KEYS)
    jobs = (run.scopes_under(JOBS) or {}).get("executions")
    if not took or not jobs:
        scopes.warn_missing("shared_kv_read_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    facts, shape = run.facts, ref.shared_kv(ref.Shape.from_config(config))
    steps = jobs * (facts["max_new"] - 1)
    least = steps * cost_bytes(
        facts["batch"], shape["readers"], shape["kv_elements"],
        facts["prompt_len"] + facts["max_new"] / 2,
        jnp.dtype(config["kv_cache_dtype"]).itemsize
    ) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / took
