"""Share of its roofline that the pallas kernel ``masked_attention``
(``ompi_tpu/ops/masked_attention.py``: a prefill's attention under the mask
of a learned index's selection, a slice of queries at a time) reaches over
the traced jobs: the least time the chip could take for the attention of
every prefill, layer and sequence, over the device time of the events that
carry the kernel's name.

A prefill's least time is the larger of its operations over the peak
bfloat16 rate and its bytes over the peak HBM rate (``costs`` below: what
the selection leaves of the algorithm, from shapes).  Operations: only the
pairs of query and key that a selection keeps, ``min(t + 1, topk)`` keys for
the query at position ``t``, two products a pair (scores, context).  Bytes:
q and the output once a query head, k and v once a K/V head; the selection
itself, however it reaches the kernel, counts nothing.  What the kernel does
beyond that is its own and lowers the share: every tile of the causal
rectangle up to a slice's end, whatever the mask leaves of it (a seeded
index keeps a quarter of every tile at the cell's length, so the kernel
skips none); K and V read again for every slice and q block; an int8 mask
of a slice's queries against the keys so far, once a K/V head.  So the share
cannot pass 100%.  At the cell's sizes the operations bind (236 GFLOP
against 149 MB a sequence and layer).

The sizes are those of the cell that was run (``run.config``,
``run.facts``); the jobs are counted by the program runs under the two host
spans of a sample, each of which prefills once.
"""

import re

KERNEL = "masked_attention"
NAMED = re.compile(r"^%?" + KERNEL + r"(\.\d+)? ")
SPANS = ("first", "full")       # the jobs of a sample; each prefills once


def pairs(seq: int, topk: int) -> int:
    """Pairs of query and key that a selection of ``topk`` keeps in a causal
    sequence of ``seq`` positions: the query at ``t`` keeps ``min(t + 1,
    topk)``."""
    whole = min(seq, topk)
    return whole * (whole + 1) // 2 + (seq - whole) * topk


def costs(batch: int, layers: int, seq: int, topk: int, heads: int,
          kv_heads: int, head_dim: int, itemsize: int) -> tuple[int, int]:
    """(operations, bytes) the attention of one prefill needs under the
    selection: two products, two operations each, a kept pair, query head
    and element of a head; q read and the output written (``heads``), k and
    v read (``kv_heads``), ``seq x head_dim`` elements a head, once."""
    operations = batch * layers * 2 * 2 * heads * head_dim * pairs(seq, topk)
    nbytes = (batch * layers * itemsize * seq * head_dim
              * (2 * heads + 2 * kv_heads))
    return operations, nbytes


def least_seconds(peaks: dict, *sizes) -> float:
    operations, nbytes = costs(*sizes)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    """The share, or None where no event carries the kernel's name (an
    untraced run, a program without the kernel, a configuration without an
    index).  Events that are not whole prefills of the cell's layers and
    slices raise: a kernel that engaged for some slices alone must not read
    like one that took them all."""
    import jax.numpy as jnp

    sa = run.config.get("sa_config")
    if run.trace is None or run.peaks is None or not sa:
        return None
    kernel_s = [e.duration_ns / 1e9 for e in run.events
                if NAMED.match(e.name)]
    if not kernel_s:
        return None
    facts, c = run.facts, run.config
    prefills = sum(run.scopes_under(span)["executions"] for span in SPANS)
    layers, seq = c["num_hidden_layers"], facts["prompt_len"]
    slices = -(-seq // sa["q_chunk_size"])
    # a call a layer, slice and group of sequences a pass holds
    groups, left = divmod(len(kernel_s), max(1, prefills * layers * slices))
    if left or not groups or facts["batch"] % groups:
        raise ValueError(
            f"masked_attention_roofline: {len(kernel_s)} kernel events are "
            f"not whole prefills ({prefills} jobs x {layers} layers x "
            f"{slices} slices x groups of the {facts['batch']} sequences) "
            f"in a run of {c.get('name')}")
    options = c.get("entry", {}).get("options", {})
    least = prefills * least_seconds(
        run.peaks, facts["batch"], layers, seq, sa["topk"],
        c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
        jnp.dtype(options.get("compute_dtype", "bfloat16")).itemsize)
    return 100.0 * least / sum(kernel_s)
