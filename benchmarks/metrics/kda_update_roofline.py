"""Share of its roofline that the cached steps' delta-rule update reaches
(Kimi Delta Attention's recurrence, ``S <- diag(alpha) S``, ``u = beta (v -
S^T k)``, ``S <- S + k u^T``, ``o = S^T q``, once a KDA layer a step): the
least time the chip could take for every update of the traced ``full`` jobs,
over the device time under the scope ``kda.update`` of the cached steps.

An update's least time is the larger of its operations over the peak
bfloat16 rate and its bytes over the peak HBM rate (``costs`` below).  It is
bound by bytes: every element of every sequence's matrix state is read once
and written once, in the type the configuration stores it in
(``kda_state_dtype``), for seven operations (the decay, two for each of the
products with k and q, two for the write).  The count is of shapes, from the
cell's own files, not of what an implementation touches, so it reads the
same work whatever later implements the update (the jnp form reads the state
twice); q, k, v, the decay and o, a hundredth of the state, count nothing.
The share cannot pass 100% while the state's read and its write both run
under that scope.

A run whose configuration names no such state, or whose trace has no time
under the scope, reads as nothing.
"""

KEYS = ["scope/kda.update@decode.step"]
SPAN = "full"       # the job that takes cached steps
JOBS = "first"      # the job that is one run of one program


def kda_layers(config: dict) -> int:
    """Layers of the configuration as it is run whose mixer is KDA."""
    return sum(layer <= config["num_hidden_layers"]
               for layer in config["linear_attn_config"]["kda_layers"])


def costs(batch: int, layers: int, heads: int, head_dim: int,
          itemsize: int) -> tuple[int, int]:
    """(operations, bytes) one cached step's updates need, all KDA layers:
    ``batch x layers x heads x head_dim x head_dim`` state elements, seven
    operations each, read once and written once."""
    elements = batch * layers * heads * head_dim * head_dim
    return 7 * elements, 2 * elements * itemsize


def least_seconds(batch: int, layers: int, heads: int, head_dim: int,
                  itemsize: int, peaks: dict) -> float:
    operations, nbytes = costs(batch, layers, heads, head_dim, itemsize)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    import jax.numpy as jnp

    from benchmarks.lib import scopes   # a traced run's, not set-up's

    group = (run.config or {}).get("linear_attn_config")
    if (run.scopes is None or run.peaks is None or not group
            or "kda_state_dtype" not in run.config):
        return None
    took = scopes.seconds(run.scopes_under(SPAN), KEYS)
    # a sample is one ``first`` job and one ``full`` job; ``first`` is one
    # program run, and ``full`` is two where the prefill is a program of its
    # own (``models/decode._two_programs``), so the jobs are counted there
    jobs = (run.scopes_under(JOBS) or {}).get("executions")
    if not took or not jobs:
        scopes.warn_missing("kda_update_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    steps = jobs * (run.facts["max_new"] - 1)
    itemsize = jnp.dtype(run.config["kda_state_dtype"]).itemsize
    least = steps * least_seconds(
        run.facts["batch"], kda_layers(run.config), group["num_heads"],
        group["head_dim"], itemsize, run.peaks)
    return 100.0 * least / took
