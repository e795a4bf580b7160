"""Operations and bytes a job needs, computed from its shapes.

These are the numerators of every utilization the benchmark prints.  They
count what the algorithm requires, not what a program happens to execute:
recomputed operations, casts and copies are not in them.
"""

from __future__ import annotations


def tree_count(tree: dict) -> int:
    """Elements in a tree of arrays or shapes-with-``size``."""
    return sum(int(leaf.size) for leaf in tree.values())


def tree_bytes(tree: dict) -> int:
    """Bytes of a tree of arrays at the type they are stored in."""
    return sum(int(leaf.size) * leaf.dtype.itemsize for leaf in tree.values())


def train_flops_per_token(n_params: int, n_layers: int, d_model: int,
                          seq: int) -> int:
    """Forward and backward of a dense decoder, PaLM's accounting: 6 per
    parameter (a tied embedding counts once, as the output projection) and
    12·L·D·S for attention's two T×T products, the causal half included.
    Recomputation under ``jax.checkpoint`` is excluded."""
    return 6 * n_params + 12 * n_layers * d_model * seq


def prefill_flops(n_params: int, vocab: int, n_layers: int, d_model: int,
                  batch: int, prompt_len: int) -> int:
    """Forward over ``batch`` prompts that ends in one token each: every
    position passes the blocks (2 per block parameter, 4·L·D·T for
    attention), and only the last position of each prompt is projected
    onto the vocabulary."""
    body = n_params - vocab * d_model
    per_token = 2 * body + 4 * n_layers * d_model * prompt_len
    return batch * prompt_len * per_token + batch * 2 * vocab * d_model


def kv_bytes(n_layers: int, batch: int, positions: float, d_model: int,
             itemsize: int) -> float:
    """Keys and values of ``positions`` cached positions, all heads."""
    return 2 * n_layers * batch * positions * d_model * itemsize


def decode_step_bytes(param_bytes: int, n_layers: int, batch: int,
                      prompt_len: int, max_new: int, d_model: int,
                      kv_itemsize: int) -> float:
    """Bytes one cached decode step must read: every parameter once at its
    stored type, and the live keys and values once at theirs.  The cache is
    live up to the position being written, so over the ``max_new - 1`` steps
    after the first token it holds ``prompt_len + max_new / 2`` positions on
    average."""
    live = prompt_len + max_new / 2
    return param_bytes + kv_bytes(n_layers, batch, live, d_model, kv_itemsize)
