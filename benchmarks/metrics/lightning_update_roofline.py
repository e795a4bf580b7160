"""Share of its roofline that the cached steps' lightning update reaches
(``S <- lam S + k v^T``, ``y = S^T q``, once a lightning layer a step): the
least time the chip could take for every update of the traced ``full`` jobs,
over the device time under the scope ``lightning.update`` of the cached
steps.

An update's least time is the larger of its operations over the peak
bfloat16 rate and its bytes over the peak HBM rate (``costs`` below).  It is
bound by bytes: every element of every sequence's matrix state is read once
and written once, in the type the configuration stores it in
(``lightning_state_dtype``), and takes five operations (the decay, the
write's product and sum, the read's product and sum).  The count is of
shapes, from the cell's own files, not of what an implementation touches, so
it reads the same work whatever later implements the update; q, k, v and y,
a hundredth of the state, count nothing.  The share cannot pass 100% while
the state's read and its write both run under that scope.

A run whose configuration names no such state, or whose trace has no time
under the scope, reads as nothing.
"""

KEYS = ["scope/lightning.update@decode.step"]
SPAN = "full"       # the job that takes cached steps
JOBS = "first"      # the job that is one run of one program


def lightning_layers(config: dict) -> int:
    """Layers of the configuration as it is run whose mixer is lightning."""
    return config["mixer_types"][:config["num_hidden_layers"]].count(
        "lightning-attn")


def costs(batch: int, layers: int, heads: int, head_dim: int,
          itemsize: int) -> tuple[int, int]:
    """(operations, bytes) one cached step's updates need, all lightning
    layers: ``batch x layers x heads x head_dim x head_dim`` state elements,
    five operations each, read once and written once."""
    elements = batch * layers * heads * head_dim * head_dim
    return 5 * elements, 2 * elements * itemsize


def least_seconds(peaks: dict, *shape) -> float:
    operations, nbytes = costs(*shape)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    import jax.numpy as jnp

    from benchmarks.lib import scopes   # a traced run's, not set-up's

    config = run.config or {}
    if (run.scopes is None or run.peaks is None
            or "lightning_state_dtype" not in config):
        return None
    took = scopes.seconds(run.scopes_under(SPAN), KEYS)
    # a sample is one ``first`` job and one ``full`` job; ``first`` is one
    # program run, and ``full`` is two where the prefill is a program of its
    # own (``models/decode._two_programs``), so the jobs are counted there
    jobs = (run.scopes_under(JOBS) or {}).get("executions")
    if not took or not jobs:
        scopes.warn_missing("lightning_update_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    steps = jobs * (run.facts["max_new"] - 1)
    least = steps * least_seconds(
        run.peaks, run.facts["batch"], lightning_layers(config),
        config["lightning_nh"], config["lightning_head_dim"],
        jnp.dtype(config["lightning_state_dtype"]).itemsize)
    return 100.0 * least / took
