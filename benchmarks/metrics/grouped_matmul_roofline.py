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

The sizes come from the configuration of the cell that was run.  A reader
is handed the run and not the cell, so the cell is found again: among the
cells that report this metric, the one whose reference lays out as many
parameters as the run held, under its batch and lengths.
"""

import math
import os
import re

KERNEL = "grouped_matmul"
NAMED = re.compile(r"^%?" + KERNEL + r"(\.\d+)? ")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


def layer_seconds(config: dict, tokens: int, peaks: dict) -> float:
    """The three matmuls of one routed layer over ``tokens`` tokens: gate
    and up (hidden to expert width), down (expert width to hidden)."""
    import jax.numpy as jnp

    rows = tokens * config["num_experts_per_tok"]
    wide, narrow = config["hidden_size"], config["intermediate_size"]
    experts = config["num_experts"]
    itemsize = jnp.dtype(config["entry"]["options"]["compute_dtype"]).itemsize
    return (2 * least_seconds(rows, wide, narrow, experts, itemsize, peaks)
            + least_seconds(rows, narrow, wide, experts, itemsize, peaks))


def config_of(run) -> dict | None:
    """The configuration of the cell this run was of (see the module's
    docstring), or None where no cell that reports the metric fits."""
    from benchmarks.lib import cells, program

    name = os.path.splitext(os.path.basename(__file__))[0]
    row = next((m for m in cells.load_benchmark(BENCH_DIR)["per_layer"]
                if m["name"] == name), None)
    for workload in (row or {}).get("workloads", []):
        cell = cells.resolve(workload, BENCH_DIR)
        table = program.param_table(program.reference(cell.config, BENCH_DIR),
                                    cell.config)
        held = sum(math.prod(dims) for dims, _std in table.values())
        same = all(cell.traffic.get(key) == run.facts.get(key)
                   for key in ("batch", "prompt_len", "max_new"))
        if held == run.facts.get("n_params") and same:
            return cell.config
    return None


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    kernel_s = [e.duration_ns / 1e9 for e in run.events
                if NAMED.match(e.name)]
    config = config_of(run) if kernel_s else None
    if config is None or "num_experts_per_tok" not in config:
        return None
    batch, layers = run.facts["batch"], config["num_hidden_layers"]
    prefill = layer_seconds(config, batch * run.facts["prompt_len"],
                            run.peaks)
    step = layer_seconds(config, batch, run.peaks)
    # a sample is two jobs: each prefills, one also takes max_new - 1 steps
    calls = 3 * layers * (2 + run.facts["max_new"] - 1)
    samples, left = divmod(len(kernel_s), calls)
    if left or not samples:     # not the jobs this was written for
        return None
    least = samples * layers * (2 * prefill
                                + (run.facts["max_new"] - 1) * step)
    return 100.0 * least / sum(kernel_s)
