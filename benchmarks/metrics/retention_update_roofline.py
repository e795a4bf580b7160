"""Share of its roofline that the cached steps' power-retention update
reaches (``S <- g S + phi(k) v^T``, ``z <- g z + phi(k)``, ``y = S^T phi(q) /
(z . phi(q) + eps)``, once a layer a step): the least time the chip could
take for every update of the traced ``full`` jobs, over the device time under
the scope ``retention.update`` of the cached steps.

An update's least time is the larger of its operations over the peak
bfloat16 rate and its bytes over the peak HBM rate (``costs`` below).  It is
bound by bytes: every element of every sequence's matrix state and of its
normaliser is read once and written once, in the type the configuration
stores them in (``retention_state_dtype``); an element of the matrix takes
three operations for the decay and the write and two for each of the query
heads that read it.  The count is of shapes, from the cell's own files
(``retention_state_dim`` is the width the configuration states it carries),
not of what an implementation touches, so it reads the same work whatever
later implements the update (the jnp form reads the state twice); q, k, v,
the gate and y, a thousandth of the state, count nothing.  The share cannot
pass 100% while the state's read and its write both run under that scope.

A run whose configuration names no such state, or whose trace has no time
under the scope, reads as nothing.
"""

KEYS = ["scope/retention.update@decode.step"]
SPAN = "full"       # the job that takes cached steps
JOBS = "first"      # the job that is one run of one program


def costs(batch: int, layers: int, kv_heads: int, query_heads: int,
          state_dim: int, head_dim: int, itemsize: int) -> tuple[int, int]:
    """(operations, bytes) one cached step's updates need, all layers:
    ``batch x layers x kv_heads x state_dim x (head_dim + 1)`` elements of
    state and normaliser, read once and written once; three operations an
    element for the decay and the write and two for each of the ``query_heads
    / kv_heads`` heads that read it."""
    elements = batch * layers * kv_heads * state_dim * (head_dim + 1)
    return ((3 + 2 * query_heads // kv_heads) * elements,
            2 * elements * itemsize)


def least_seconds(peaks: dict, *shape) -> float:
    operations, nbytes = costs(*shape)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    import jax.numpy as jnp

    from benchmarks.lib import scopes   # a traced run's, not set-up's

    config = run.config or {}
    if (run.scopes is None or run.peaks is None
            or "retention_state_dim" not in config):
        return None
    took = scopes.seconds(run.scopes_under(SPAN), KEYS)
    # a sample is one ``first`` job and one ``full`` job, each one run of one
    # program: the jobs are counted under ``first``
    jobs = (run.scopes_under(JOBS) or {}).get("executions")
    if not took or not jobs:
        scopes.warn_missing("retention_update_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    steps = jobs * (run.facts["max_new"] - 1)
    least = steps * least_seconds(
        run.peaks, run.facts["batch"], config["num_hidden_layers"],
        config["num_key_value_heads"], config["num_attention_heads"],
        config["retention_state_dim"], config["head_dim"],
        jnp.dtype(config["retention_state_dtype"]).itemsize)
    return 100.0 * least / took
