"""Share of their roofline that the pallas flash-attention kernels
(``ompi_tpu/ops/flash_attention.py``: ``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``) reach over the traced samples of a training cell: the
least time the chip could take for the attention of every layer and step,
over the device time of the events that carry the kernels' names.

A pass's least time is the larger of its operations over the peak bfloat16
rate and its bytes over the peak HBM rate (``costs`` below: what the causal
algorithm needs, from shapes).  Operations: only the pairs of query and key
that the mask leaves, the triangle and not the square, two products a pair
forward (scores, context) and five backward (scores again, dO·Vᵀ, and the
three gradients).  Bytes: q, k, v read and the output written once forward;
q, k, v, the output and its gradient read and the three gradients written
once backward.  What the kernels do beyond that is their own and lowers the
share: the masked half of the blocks the diagonal crosses, the second
rebuild of the scores in the two-kernel backward, K and V read again for
every q block, a forward that runs again where the checkpoint policy does
not keep its result.  So the share cannot pass 100%.  At the cells' head
width the operations bind (cell 1: 137 GFLOP against 268 MB a layer
forward).

The sizes are those of the cell that was run: its configuration's file and
its traffic mix's, which the run carries (``run.config``, ``run.traffic``),
and the layers that attend as its reference counts them
(``facts["counts"]["attention_layers"]``).
"""

import re

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
NAMED = re.compile(r"^%?(" + "|".join(KERNELS) + r")(\.\d+)? ")


def costs(batch: int, heads: int, seq: int, head_dim: int,
          itemsize: int) -> tuple[tuple[int, int], tuple[int, int]]:
    """((operations, bytes) forward, (operations, bytes) backward) that
    causal attention needs over ``batch`` sequences of ``seq`` positions and
    ``heads`` heads: a query meets the keys at or before it, seq·(seq+1)/2
    pairs a head; a product is two operations a pair and element of the
    head; an operand is batch·seq·heads·head_dim elements."""
    pairs = batch * heads * seq * (seq + 1) // 2
    operand = itemsize * batch * seq * heads * head_dim
    return ((2 * 2 * head_dim * pairs, 4 * operand),
            (5 * 2 * head_dim * pairs, 8 * operand))


def least_seconds(batch: int, heads: int, seq: int, head_dim: int,
                  itemsize: int, peaks: dict) -> tuple[float, float]:
    """The least time of one forward and of one backward (both its
    kernels) of ``costs``' attention on a chip with these peaks."""
    return tuple(max(operations / peaks["bf16_flops"],
                     nbytes / peaks["hbm_bytes_per_s"])
                 for operations, nbytes in costs(batch, heads, seq, head_dim,
                                                 itemsize))


def device_shape(config: dict, batch: int, seq: int) -> tuple:
    """(batch, heads, seq, head_dim, itemsize) of one layer's attention on
    one device of the configuration's mesh: the batch split over ``dp``,
    the heads over ``tp`` and (Ulysses) ``sp``."""
    import jax.numpy as jnp

    mesh = config.get("mesh", {})
    heads = config["num_attention_heads"]
    options = config.get("entry", {}).get("options", {})
    return (batch // mesh.get("dp", 1),
            heads // (mesh.get("tp", 1) * mesh.get("sp", 1)), seq,
            config["hidden_size"] // heads,
            jnp.dtype(options.get("compute_dtype", "bfloat16")).itemsize)


def read(run):
    """The share, or None where no event carries a kernel's name (the
    kernels are absent: an untraced run, the parent's program).  Events that
    do not add up to whole steps of the cell's layers raise: kernels that
    engaged in part, or under another count, must not read like kernels
    that are absent."""
    if run.trace is None or run.peaks is None:
        return None
    seconds = {kernel: [] for kernel in KERNELS}
    for event in run.events:
        match = NAMED.match(event.name)
        if match:
            seconds[match.group(1)].append(event.duration_ns / 1e9)
    if not any(seconds.values()):
        return None
    counts = {kernel: len(times) for kernel, times in seconds.items()}
    chips = run.facts["chips"]
    # a kernel's calls a step: once a layer that attends, on every chip
    calls = run.facts["counts"]["attention_layers"] * chips
    forwards, dqs, dkvs = (counts[kernel] for kernel in KERNELS)
    steps, left = divmod(dqs, calls)
    # each backward kernel once a layer and step; the forward once, or
    # twice where it is recomputed (the second is the kernels' own)
    if left or not steps or dkvs != dqs or forwards not in (dqs, 2 * dqs):
        raise ValueError(f"flash_attention_roofline: kernel events {counts} "
                         f"are not whole steps of {calls} calls a kernel "
                         f"in a run of {run.config.get('name')} on {chips} "
                         f"chip(s)")
    forward, backward = least_seconds(
        *device_shape(run.config, run.traffic["batch"], run.traffic["seq"]),
        run.peaks)
    least = steps * calls * (forward + backward)
    return 100.0 * least / sum(sum(times) for times in seconds.values())
