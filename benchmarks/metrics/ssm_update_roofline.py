"""Share of its roofline that the cached steps' state update reaches (the
recurrence of a state-space mixer, ``h <- exp(dt A) h + dt x (x) B``, ``y = h
C``, once a layer a step): the least time the chip could take for every
update of the traced ``full`` jobs, over the device time under the scope
``ssm.update`` of the cached steps.

An update's least time is the larger of its operations over the peak
bfloat16 rate and its bytes over the peak HBM rate (``costs`` below).  It is
bound by bytes: every element of every sequence's state is read once and
written once, in the type the configuration stores it in
(``ssm_state_dtype``), for five operations.  The count is of shapes, from
the cell's own files, not of what an implementation touches, so it reads the
same work whatever later implements the update; B, C, x, dt and y, a
thousandth of the state, count nothing.  The share cannot pass 100% while
the state's read and its write both run under that scope.

A run whose configuration names no such state, or whose trace has no time
under the scope, reads as nothing.
"""

KEYS = ["scope/ssm.update@decode.step"]
SPAN = "full"       # the job that takes cached steps


def costs(batch: int, layers: int, d_ssm: int, d_state: int,
          itemsize: int) -> tuple[int, int]:
    """(operations, bytes) one cached step's updates need, all layers:
    ``batch x layers x d_ssm x d_state`` state elements (heads x head width
    = ``d_ssm``), each decayed, added to and multiplied into ``y`` (five
    operations), read once and written once."""
    elements = batch * layers * d_ssm * d_state
    return 5 * elements, 2 * elements * itemsize


def least_seconds(batch: int, layers: int, d_ssm: int, d_state: int,
                  itemsize: int, peaks: dict) -> float:
    operations, nbytes = costs(batch, layers, d_ssm, d_state, itemsize)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    import jax.numpy as jnp

    from benchmarks.lib import scopes   # a traced run's, not set-up's

    sizes = [run.config.get(key) for key in (
        "num_hidden_layers", "mamba_d_ssm", "mamba_d_state")]
    if (run.scopes is None or run.peaks is None or None in sizes
            or "ssm_state_dtype" not in run.config):
        return None
    table = run.scopes_under(SPAN)
    took = scopes.seconds(table, KEYS)
    if not took or not table["executions"]:
        scopes.warn_missing("ssm_update_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    steps = table["executions"] * (run.facts["max_new"] - 1)
    itemsize = jnp.dtype(run.config["ssm_state_dtype"]).itemsize
    least = steps * least_seconds(run.facts["batch"], *sizes, itemsize,
                                  run.peaks)
    return 100.0 * least / took
