"""Share of its roofline that the cached steps' read of the latent cache
reaches: the least time the chip could take to read every latent layer's live
cached rows once a step, over the steps of the traced ``full`` jobs, over the
device time under the scope ``attention`` of the cached steps.

A step's least time in its latent layers is bound by bytes (``costs`` below):
every live position's cached row (``kv_lora_rank + qk_rope_head_dim``
elements for all heads, at ``kv_cache_dtype``) is read once for every
sequence and latent layer; against each row a query head does ``2 x (rank +
rope)`` operations for its score and ``2 x rank`` for the context, sixteen
heads to a row, which the MXU does in a tenth of the time the row takes to
arrive.  The cache is live up to the position being written: over the
``max_new - 1`` steps after the first token it holds ``prompt_len + max_new /
2`` positions on the mean.  The count is of shapes, from the cell's own files,
not of what an implementation touches: a step that reads the cache once for
its scores and once more for its context takes two reads' time under the
scope and cannot pass 50%; one pass that keeps a tile of rows for both would
read what is counted.  The share cannot pass 100% while the rows' read runs
under that scope.

A run whose configuration names no latent, or whose trace has no time under
the scope, reads as nothing.
"""

KEYS = ["scope/attention@decode.step"]
SPAN = "full"       # the job that takes cached steps
JOBS = "first"      # the job that is one run of one program


def costs(batch: int, layers: int, heads: int, rank: int, rope: int,
          positions: float, itemsize: int) -> tuple[float, float]:
    """(operations, bytes) one cached step needs in its latent layers: each
    live row read once, and a score and a context's term a head against it."""
    rows = batch * layers * positions
    return (rows * heads * 2 * (2 * rank + rope),
            rows * (rank + rope) * itemsize)


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
    took = scopes.seconds(run.scopes_under(SPAN), KEYS)
    # a sample is one ``first`` job and one ``full`` job; ``first`` is one
    # program run, and ``full`` is two where the prefill is a program of its
    # own (``models/decode._two_programs``), so the jobs are counted there
    jobs = (run.scopes_under(JOBS) or {}).get("executions")
    if not took or not jobs:
        scopes.warn_missing("latent_read_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    facts = run.facts
    steps = jobs * (facts["max_new"] - 1)
    least = steps * least_seconds(
        run.peaks, facts["batch"], facts["counts"]["attention_layers"],
        config["num_attention_heads"], config["kv_lora_rank"],
        config["qk_rope_head_dim"],
        facts["prompt_len"] + facts["max_new"] / 2,
        jnp.dtype(config["kv_cache_dtype"]).itemsize)
    return 100.0 * least / took
