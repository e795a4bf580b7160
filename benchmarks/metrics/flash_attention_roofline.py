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

The sizes come from the configuration of the cell that was run, found as
``grouped_matmul_roofline`` finds its own: among the cells that report this
metric, the one whose reference lays out as many parameters as the run held,
on as many chips, with as many tokens a step.
"""

import math
import os
import re

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
NAMED = re.compile(r"^%?(" + "|".join(KERNELS) + r")(\.\d+)? ")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def cell_of(run):
    """The cell this run was of (see the module's docstring), or None."""
    from benchmarks.lib import cells, program

    name = os.path.splitext(os.path.basename(__file__))[0]
    row = next((m for m in cells.load_benchmark(BENCH_DIR)["per_layer"]
                if m["name"] == name), None)
    for workload in (row or {}).get("workloads", []):
        cell = cells.resolve(workload, BENCH_DIR)
        table = program.param_table(program.reference(cell.config, BENCH_DIR),
                                    cell.config)
        held = sum(math.prod(dims) for dims, _std in table.values())
        tokens = cell.traffic.get("batch", 0) * cell.traffic.get("seq", 0)
        if (held == run.facts.get("n_params")
                and cell.chips == run.facts.get("chips")
                and tokens == run.facts.get("tokens_per_sample")):
            return cell
    return None


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
    cell = cell_of(run)
    if cell is None:
        raise ValueError(f"flash_attention_roofline: kernel events {counts} "
                         f"in a run that is of none of this metric's cells "
                         f"({run.facts})")
    calls = cell.config["num_hidden_layers"] * cell.chips  # a step, a kernel
    forwards, dqs, dkvs = (counts[kernel] for kernel in KERNELS)
    steps, left = divmod(dqs, calls)
    # each backward kernel once a layer and step; the forward once, or
    # twice where it is recomputed (the second is the kernels' own)
    if left or not steps or dkvs != dqs or forwards not in (dqs, 2 * dqs):
        raise ValueError(f"flash_attention_roofline: kernel events {counts} "
                         f"are not whole steps of {calls} calls a kernel "
                         f"in {cell.name}")
    forward, backward = least_seconds(
        *device_shape(cell.config, cell.traffic["batch"],
                      cell.traffic["seq"]), run.peaks)
    least = steps * calls * (forward + backward)
    return 100.0 * least / sum(sum(times) for times in seconds.values())
