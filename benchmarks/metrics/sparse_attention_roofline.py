"""Share of its roofline that the cached steps' sparse attention reaches (a
learned index scores every live position, the ``topk`` largest are selected,
their K and V rows are read out of the carry and attended over, once a layer
a step): the least time the chip could take for all of it over the traced
``full`` jobs, over the device time under the four scopes that do it,
``index.score``, ``index.select``, ``attention.gather`` and ``attention`` of
the cached steps.

A step's least time is the larger of its operations over the peak bfloat16
rate and its bytes over the peak HBM rate (``costs`` below).  It is bound by
bytes: the index key of every live position once, and the K and the V row of
each selected position once, in the type the configuration caches them in.
The count is of shapes, from the cell's own files, not of what an
implementation touches, so it reads the same work whatever later implements
the step: a program that gathers the rows and then reads them again moves
them twice and reads half the share; the queries, the scores and the chosen
positions, a hundredth of the rows, count nothing.  The share cannot pass
100% while all four scopes are summed.

A run whose configuration names no index, or whose trace has no time under
the scopes, reads as nothing.
"""

KEYS = ["scope/index.score@decode.step", "scope/index.select@decode.step",
        "scope/attention.gather@decode.step", "scope/attention@decode.step"]
SPAN = "full"       # the job that takes cached steps


def costs(batch: int, layers: int, live: float, index_heads: int,
          index_dim: int, topk: int, heads: int, kv_heads: int, head_dim: int,
          itemsize: int) -> tuple[float, float]:
    """(operations, bytes) one cached step's selection and attention need,
    all layers, with ``live`` positions cached a sequence.  Scores: two
    operations a live position, index head and element of the index key,
    every live position's key (``index_dim`` elements) read once.
    Attention: over the ``min(topk, live)`` selected positions, four
    operations a position, query head and element of a head (scores and
    context), each position's K and V row (``kv_heads x head_dim`` elements
    each) read once."""
    picked = min(topk, live)
    operations = batch * layers * (2 * index_heads * index_dim * live
                                   + 4 * heads * head_dim * picked)
    nbytes = batch * layers * itemsize * (
        index_dim * live + 2 * kv_heads * head_dim * picked)
    return operations, nbytes


def least_seconds(peaks: dict, *sizes) -> float:
    operations, nbytes = costs(*sizes)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    import jax.numpy as jnp

    from benchmarks.lib import scopes   # a traced run's, not set-up's

    sa = run.config.get("sa_config")
    if (run.scopes is None or run.peaks is None or not sa
            or "kv_cache_dtype" not in run.config):
        return None
    table = run.scopes_under(SPAN)
    took = scopes.seconds(table, KEYS)
    if not took or not table["executions"]:
        scopes.warn_missing("sparse_attention_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    facts, c = run.facts, run.config
    steps = table["executions"] * (facts["max_new"] - 1)
    # the cache is live up to the position being written
    live = facts["prompt_len"] + facts["max_new"] / 2
    least = steps * least_seconds(
        run.peaks, facts["batch"], c["num_hidden_layers"], live,
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
        c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
        jnp.dtype(c["kv_cache_dtype"]).itemsize)
    return 100.0 * least / took
