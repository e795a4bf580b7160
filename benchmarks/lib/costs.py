"""Operations and bytes a job needs, computed from its shapes.

These are the numerators of every utilization the benchmark prints.  They
count what the algorithm requires, not what a program happens to execute:
recomputed operations, casts and copies are not in them.
"""

from __future__ import annotations


def tree_count(tree: dict) -> int:
    """Elements in a tree of arrays or shapes-with-``size``."""
    return sum(int(leaf.size) for leaf in tree.values())


def step_param_bytes(n_params: int, lookup_params: int, stored_itemsize: int,
                     compute_itemsize: int) -> int:
    """Bytes of the parameters that one cached decode step must read: every
    stored parameter but a table the step only looks rows up in
    (``lookup_params`` of the reference's ``counts``: a few rows a step, which
    count nothing; a table that is also the projection is read whole and is
    not one), at the narrower of the type it is stored in and the type the
    step computes in: a step that multiplies in bfloat16 reads the bfloat16
    copy of a float32 leaf, half its bytes."""
    return (n_params - lookup_params) * min(stored_itemsize, compute_itemsize)


def train_flops_per_token(active_params: int, attention_layers: int,
                          attention_width: int, seq: int) -> int:
    """Forward and backward of a decoder, PaLM's accounting: 6 per parameter
    a token multiplies (``counts(shape)["active_params"]`` of the
    configuration's reference: in a dense model every parameter, a tied
    embedding once, as the output projection; in a routed one the experts a
    token is sent to) and 12·L·D·S for attention's two T×T products, the
    causal half included: L the layers that attend and D the summed width
    of their query heads (``attention_layers``, ``attention_width`` of the
    same ``counts``).  Recomputation under ``jax.checkpoint`` is
    excluded."""
    return 6 * active_params + 12 * attention_layers * attention_width * seq


def prefill_flops(active_params: int, projection_params: int,
                  attention_layers: int, attention_width: int, batch: int,
                  prompt_len: int) -> int:
    """Forward over ``batch`` prompts that ends in one token each: every
    position passes the blocks (2 per active block parameter, 4·L·D·T for
    attention in the L layers that attend, D wide), and only the last
    position of each prompt is projected onto the vocabulary
    (``projection_params``: ``vocab x d_model``)."""
    body = active_params - projection_params
    per_token = (2 * body
                 + 4 * attention_layers * attention_width * prompt_len)
    return batch * prompt_len * per_token + batch * 2 * projection_params


def kv_bytes(attention_layers: int, batch: int, positions: float,
             kv_elements: int, itemsize: int) -> float:
    """Keys and values of ``positions`` cached positions in the layers that
    attend; ``kv_elements`` is what one position holds of both in one such
    layer (``2 x d_model`` with as many K/V heads as query heads)."""
    return attention_layers * batch * positions * kv_elements * itemsize


def decode_step_bytes(param_bytes: int, attention_layers: int, batch: int,
                      prompt_len: int, max_new: int, kv_elements: int,
                      kv_itemsize: int, state_elements: int = 0) -> float:
    """Bytes one cached decode step must read: the parameters it reads, once,
    at the type it reads them in (``param_bytes``: ``step_param_bytes``), the
    live keys and values once at theirs, and what a sequence holds of
    fixed-size state (``state_elements``, over all layers: a convolution's
    last positions, a linear attention's matrix) once at the cache's type.
    The cache is live up to the position being written, so over the
    ``max_new - 1`` steps after the first token it holds ``prompt_len +
    max_new / 2`` positions on average: what the algorithm needs, not what a
    program that reads a padded cache touches.  A routed model's parameters
    are counted whole, every expert's matrices once a step: that is what a
    step reads where its choices (batch x experts a token) are several times
    the experts, and more than it reads in a cell under that, whose share
    then passes 100%."""
    live = prompt_len + max_new / 2
    return (param_bytes
            + kv_bytes(attention_layers, batch, live, kv_elements,
                       kv_itemsize)
            + batch * state_elements * kv_itemsize)
