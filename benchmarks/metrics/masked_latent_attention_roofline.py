"""Share of its roofline that the pallas kernel ``masked_latent_attention``
(``ompi_tpu/ops/masked_latent_attention.py``: a prefill's attention under the
mask of an index's selection in a latent layer, a slice of queries at a time
against the expanded keys and values) reaches over the traced jobs: the least
time the chip could take for the selected attention of every prefill, layer
and sequence, over the device time of the events that carry the kernel's
name.

A prefill's least time is the larger of its operations over the peak
bfloat16 rate and its bytes over the peak HBM rate (``costs`` below: what
the selection leaves of the algorithm, from shapes).  Operations: only the
pairs of query and key that a selection keeps, ``min(t + 1, topk)`` keys for
the query at position ``t``; a pair is ``2 x (nope + rope)`` operations a
head for its score and ``2 x v_dim`` for its context.  Bytes: a head's
queries and its output once, its keys and values once, the shared key part
once; the selection itself, however it reaches the kernel, counts nothing.
What the kernel does beyond that is its own and lowers the share: every tile
of the causal rectangle up to a slice's end, whatever the mask leaves of it
(a seeded index keeps an eighth of every tile at the cell's length, so the
kernel could skip none; ``ROADMAP.md`` M18); the keys and values read again
for every slice; an int8 mask of a slice's queries against the keys so far,
once a group of four heads.  So the share cannot pass 100%.  At the cell's
sizes the operations bind.

The sizes are those of the cell that was run (``run.config``,
``run.facts``); the samples are counted by the program runs under the host
span ``first`` (one run of the prefill's program; the ``full`` job runs it
again and then the generating one), so a sample prefills twice.
"""

import re

KERNEL = "masked_latent_attention"
NAMED = re.compile(r"^%?" + KERNEL + r"(\.\d+)? ")
JOBS = "first"      # the job that is one run of one program, the prefill's


def pairs(seq: int, topk: int) -> int:
    """Pairs of query and key that a selection of ``topk`` keeps in a causal
    sequence of ``seq`` positions: the query at ``t`` keeps ``min(t + 1,
    topk)``."""
    whole = min(seq, topk)
    return whole * (whole + 1) // 2 + (seq - whole) * topk


def costs(batch: int, layers: int, seq: int, topk: int, heads: int,
          nope: int, rope: int, v_dim: int, itemsize: int) -> tuple[int, int]:
    """(operations, bytes) the attention of one prefill needs under the
    selection."""
    operations = (batch * layers * heads * 2 * (nope + rope + v_dim)
                  * pairs(seq, topk))
    nbytes = batch * layers * itemsize * seq * (
        heads * (2 * (nope + v_dim) + rope + v_dim) + rope)
    return operations, nbytes


def least_seconds(peaks: dict, *sizes) -> float:
    operations, nbytes = costs(*sizes)
    return max(operations / peaks["bf16_flops"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    """The share, or None where no event carries the kernel's name (an
    untraced run, a program without the kernel, a configuration without an
    index in a latent layer)."""
    import jax.numpy as jnp

    c = run.config or {}
    if (run.trace is None or run.peaks is None or "index_topk" not in c
            or "kv_lora_rank" not in c):
        return None
    kernel_s = [e.duration_ns / 1e9 for e in run.events
                if NAMED.match(e.name)]
    if not kernel_s:
        return None
    facts = run.facts
    prefills = 2 * (run.scopes_under(JOBS) or {}).get("executions", 0)
    layers, seq = facts["counts"]["attention_layers"], facts["prompt_len"]
    whole, tail = divmod(seq, c["index_q_slice"])
    calls = prefills * layers * facts["batch"] * (whole + bool(tail))
    # a call a layer, slice and sequence where a pass holds one sequence; a
    # pass of more holds them in one call
    if not calls or calls % len(kernel_s):
        raise ValueError(
            f"masked_latent_attention_roofline: {len(kernel_s)} kernel "
            f"events are not whole prefills ({prefills} jobs x {layers} "
            f"layers x {whole + bool(tail)} slices x passes of the "
            f"{facts['batch']} sequences) in a run of {c.get('name')}")
    options = c.get("entry", {}).get("options", {})
    least = prefills * least_seconds(
        run.peaks, facts["batch"], layers, seq, c["index_topk"],
        c["num_attention_heads"], c["qk_nope_head_dim"],
        c["qk_rope_head_dim"], c["v_head_dim"],
        jnp.dtype(options.get("compute_dtype", "bfloat16")).itemsize)
    return 100.0 * least / sum(kernel_s)
