"""Share of its roofline that a cached step's selected read of the latent
cache reaches, in a latent layer with an index: the least time the chip could
take to score a step's query against every live index key and to read the
selected latent rows once, over the steps of the traced ``full`` jobs, over
the device time under the scopes ``latent_index.score``,
``latent_index.select`` and
``attention.selected`` of the cached steps.

A step's least time there is bound by bytes (``costs`` below): every live
position's index key (``index_head_dim`` elements, at ``kv_cache_dtype``) is
read once a sequence and layer, and of the latent cache the ``index_topk``
selected rows alone (``kv_lora_rank + qk_rope_head_dim`` elements for all
heads; every live row while the cache is no longer than the selection).
Against an index key the ``index_n_heads`` heads do ``2 x index_head_dim``
operations each; against a selected row a query head does ``2 x (rank +
rope)`` for its score and ``2 x rank`` for the context.  The cache is live up
to the position being written: ``prompt_len + max_new / 2`` positions on the
mean over a job's cached steps.  The count is of shapes, from the cell's own
files, not of what an implementation touches: a step that streams every live
latent row under the selection's mask (``ops/latent_decode.py`` with
``chosen``: the rows are shared by all heads, so the stream costs this chip
less than a gather up to eight selections of cache) reads ``live / topk``
times the counted rows and cannot pass the share that ratio leaves; the
bisection's passes over the scores count nothing.  So the share cannot pass
100% while the index's scores, the selection and the read run under those
scopes.

A run whose configuration names no index inside a latent layer, or whose
trace has no time under the scopes, reads as nothing.
"""

KEYS = ["scope/latent_index.score@decode.step",
        "scope/latent_index.select@decode.step",
        "scope/attention.selected@decode.step"]
SPAN = "full"       # the job that takes cached steps
JOBS = "first"      # the job that is one run of one program


def costs(batch: int, layers: int, heads: int, rank: int, rope: int,
          index_heads: int, index_dim: int, topk: int, positions: float,
          itemsize: int) -> tuple[float, float]:
    """(operations, bytes) one cached step needs for its selection and its
    selected read: every live index key once, the selected rows once."""
    selected = min(positions, topk)
    operations = batch * layers * (
        positions * index_heads * 2 * index_dim
        + selected * heads * 2 * (2 * rank + rope))
    nbytes = batch * layers * itemsize * (
        positions * index_dim + selected * (rank + rope))
    return operations, nbytes


def least_seconds(peaks: dict, *shape) -> float:
    operations, nbytes = costs(*shape)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    import jax.numpy as jnp

    from benchmarks.lib import scopes   # a traced run's, not set-up's

    config = run.config or {}
    if (run.scopes is None or run.peaks is None
            or "kv_lora_rank" not in config or "index_topk" not in config):
        return None
    took = scopes.seconds(run.scopes_under(SPAN), KEYS)
    jobs = (run.scopes_under(JOBS) or {}).get("executions")
    if not took or not jobs:
        scopes.warn_missing("selected_latent_read_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    facts = run.facts
    steps = jobs * (facts["max_new"] - 1)
    least = steps * least_seconds(
        run.peaks, facts["batch"], facts["counts"]["attention_layers"],
        config["num_attention_heads"], config["kv_lora_rank"],
        config["qk_rope_head_dim"], config["index_n_heads"],
        config["index_head_dim"], config["index_topk"],
        facts["prompt_len"] + facts["max_new"] / 2,
        jnp.dtype(config["kv_cache_dtype"]).itemsize)
    return 100.0 * least / took
