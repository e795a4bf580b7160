"""Share of its roofline that the prefill's causal latent attention reaches:
the least time the chip could take for the attention of every latent layer of
the traced ``first`` jobs' prompts, over the device time under the scope
``attention`` of the prefill, whatever implements it.

A prompt's least time in one latent layer is bound by operations (``costs``
below): a query attends to itself and every earlier position, ``T (T + 1) /
2`` pairs, and a pair costs a head a score over ``qk_nope_head_dim +
qk_rope_head_dim`` elements and a context's term over ``v_head_dim``, two
operations an element; the bytes (queries, a head's keys and values, the
output, once) are a thousandth of that time at 16k positions.  The count is
of the causal triangle alone: a form that computes the square and masks half
of it takes twice the time and cannot pass 50%; what the scope holds beside
the products (a re-layout of the queries, padding to whole tiles) counts as
time and not as work.  The share cannot pass 100% while the products run
under that scope.

A run whose configuration names no latent, or whose trace has no time under
the scope, reads as nothing.
"""

KEYS = ["scope/attention@prefill"]
SPAN = "first"      # the job that is one run of the prefill's program


def costs(batch: int, layers: int, heads: int, key_width: int,
          value_width: int, prompt_len: int, itemsize: int
          ) -> tuple[float, float]:
    """(operations, bytes) the prompts' causal attention needs, all latent
    layers: two operations an element of a pair's score and of its term of
    the context; q, k, v and the output once."""
    pairs = prompt_len * (prompt_len + 1) / 2
    rows = batch * layers * heads
    return (rows * pairs * 2 * (key_width + value_width),
            rows * prompt_len * 2 * (key_width + value_width) * itemsize)


def least_seconds(peaks: dict, *shape) -> float:
    operations, nbytes = costs(*shape)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    import jax.numpy as jnp

    from benchmarks.lib import scopes   # a traced run's, not set-up's

    config = run.config or {}
    if (run.scopes is None or run.peaks is None
            or "kv_lora_rank" not in config):
        return None
    table = run.scopes_under(SPAN)
    took = scopes.seconds(table, KEYS)
    if not took or not table.get("executions"):
        scopes.warn_missing("latent_prefill_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    facts = run.facts
    compute = config["entry"]["options"].get("compute_dtype",
                                             config["param_dtype"])
    least = table["executions"] * least_seconds(
        run.peaks, facts["batch"], facts["counts"]["attention_layers"],
        config["num_attention_heads"],
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        config["v_head_dim"], facts["prompt_len"],
        jnp.dtype(compute).itemsize)
    return 100.0 * least / took
