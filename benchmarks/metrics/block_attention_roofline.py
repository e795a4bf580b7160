"""Share of its roofline that the cached steps' block selection and the
attention under it reach: the least time the chip could take for every
selected layer of every step of the traced ``full`` jobs, over the device
time under the scopes ``blocks.score``, ``blocks.select``,
``attention.gather`` and ``attention`` of the cached steps.

A step's least time in a selected layer is the larger of its operations
over the peak bfloat16 rate and its bytes over the peak HBM rate (``costs``
below).  It is bound by bytes: the live pooled keys are read once (one of
``head_dim`` elements a K/V head for every ``kernel_stride`` positions: a
query's scores need every one) and ``topk x block_size`` rows of K and of V
a sequence and K/V head once, at ``kv_cache_dtype``.  The cache is live up
to the position being written: over the ``max_new - 1`` steps after the
first token it holds ``prompt_len + max_new / 2`` positions on the mean.
The count is of what the selection needs, not of what an implementation
touches: a step that streams the layer's whole K and V under a mask reads
``positions / (topk x block_size)`` times the rows counted here, and its
share says so; a fetch of the chosen blocks by number would read what is
counted.  The share cannot pass 100% while the rows' read runs under those
scopes.

A run whose configuration names no selection, or whose trace has no time
under the scopes, reads as nothing.
"""

KEYS = ["scope/blocks.score@decode.step", "scope/blocks.select@decode.step",
        "scope/attention.gather@decode.step", "scope/attention@decode.step"]
SPAN = "full"       # the job that takes cached steps
JOBS = "first"      # the job that is one run of one program


def selected_layers(config: dict) -> int:
    """Layers of the configuration as it is run whose mixer selects."""
    return config["mixer_types"][:config["num_hidden_layers"]].count(
        "minicpm4")


def costs(batch: int, layers: int, heads: int, kv_heads: int, head_dim: int,
          positions: float, stride: int, topk: int, block: int,
          itemsize: int) -> tuple[float, float]:
    """(operations, bytes) one cached step needs in its selected layers: a
    query head's product with every live pooled key, and two products with
    each of the ``topk x block`` selected positions; the pooled keys and the
    selected rows of K and V read once."""
    pooled = positions / stride
    selected = min(topk * block, positions)
    operations = batch * layers * heads * head_dim * 2 * (
        pooled + 2 * selected)
    nbytes = batch * layers * kv_heads * head_dim * itemsize * (
        pooled + 2 * selected)
    return operations, nbytes


def least_seconds(peaks: dict, *shape) -> float:
    operations, nbytes = costs(*shape)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    import jax.numpy as jnp

    from benchmarks.lib import scopes   # a traced run's, not set-up's

    config = run.config or {}
    sizes = config.get("sparse_config")
    if (run.scopes is None or run.peaks is None or not sizes
            or "mixer_types" not in config):
        return None
    took = scopes.seconds(run.scopes_under(SPAN), KEYS)
    jobs = (run.scopes_under(JOBS) or {}).get("executions")
    if not took or not jobs:
        scopes.warn_missing("block_attention_roofline", KEYS,
                            where=f" in the runs under the host span "
                                  f"{SPAN!r}")
        return None
    facts = run.facts
    steps = jobs * (facts["max_new"] - 1)
    least = steps * least_seconds(
        run.peaks, facts["batch"], selected_layers(config),
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], facts["prompt_len"] + facts["max_new"] / 2,
        sizes["kernel_stride"], sizes["topk"], sizes["block_size"],
        jnp.dtype(config["kv_cache_dtype"]).itemsize)
    return 100.0 * least / took
