"""Share of its roofline that the pallas kernel ``grouped_matmul`` (the
routed experts' matmuls, ``ompi_tpu/ops/grouped_matmul.py``) reaches over the
traced samples: the least time the chip could take for every call of it,
over the device time of the events that carry the kernel's name.

A call's least time is the larger of its operations over the peak bfloat16
rate and its bytes over the peak HBM rate (``costs`` below: what the
algorithm needs, from shapes; the rows that only fill a tile up, and a
second read of any matrix, are the kernel's own and not counted).  The
prefill's calls are bound by operations (thousands of rows an expert), the
cached step's by bytes (a handful of rows an expert, every expert's matrix
read once), so one share over both says how near the kernel is to whichever
bound applies where it runs.

The sizes are the run's own: the routed layers' shape as the
configuration's reference counts it (``facts["counts"]["routed"]``, so an
expert's width may sit under any key of a configuration's file and some
layers may have no experts) and the type the cell's configuration computes
in.  A run whose reference names no routed layers reads as nothing.
"""

import re

KERNEL = "grouped_matmul"
NAMED = re.compile(r"^%?" + KERNEL + r"(\.\d+)? ")


def costs(rows: int, k_dim: int, n_dim: int, groups: int,
          itemsize: int) -> tuple[int, int]:
    """(operations, bytes) one grouped matmul needs: ``rows`` real rows of
    ``k_dim`` against one of ``groups`` (k_dim, n_dim) matrices each.  Two
    operations a row and weight; every row read and its result written
    once; every matrix that can have a row (no more than there are rows)
    read once."""
    operations = 2 * rows * k_dim * n_dim
    touched = min(groups, rows)
    nbytes = itemsize * (rows * (k_dim + n_dim) + touched * k_dim * n_dim)
    return operations, nbytes


def least_seconds(rows: int, k_dim: int, n_dim: int, groups: int,
                  itemsize: int, peaks: dict) -> float:
    operations, nbytes = costs(rows, k_dim, n_dim, groups, itemsize)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def layer_seconds(routed: dict, itemsize: int, tokens: int,
                  peaks: dict) -> float:
    """The three matmuls of one routed layer over ``tokens`` tokens: gate
    and up (hidden to expert width), down (expert width to hidden)."""
    rows = tokens * routed["top_k"]
    wide, narrow = routed["d_model"], routed["d_expert"]
    experts = routed["experts"]
    return (2 * least_seconds(rows, wide, narrow, experts, itemsize, peaks)
            + least_seconds(rows, narrow, wide, experts, itemsize, peaks))


def read(run):
    import jax.numpy as jnp

    if run.trace is None or run.peaks is None:
        return None
    kernel_s = [e.duration_ns / 1e9 for e in run.events
                if NAMED.match(e.name)]
    routed = run.facts["counts"].get("routed")
    if not kernel_s or routed is None:
        return None
    itemsize = jnp.dtype(
        run.config["entry"]["options"]["compute_dtype"]).itemsize
    batch, layers = run.facts["batch"], routed["layers"]
    prefill = layer_seconds(routed, itemsize,
                            batch * run.facts["prompt_len"], run.peaks)
    step = layer_seconds(routed, itemsize, batch, run.peaks)
    # a sample is two jobs: each prefills, one also takes max_new - 1 steps
    calls = 3 * layers * (2 + run.facts["max_new"] - 1)
    samples, left = divmod(len(kernel_s), calls)
    if left or not samples:     # not the jobs this was written for
        return None
    least = samples * layers * (2 * prefill
                                + (run.facts["max_new"] - 1) * step)
    return 100.0 * least / sum(kernel_s)
