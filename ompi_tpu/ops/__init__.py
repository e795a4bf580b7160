"""Hand-written TPU kernels (pallas) for the framework's hot ops.

The reference keeps its hot loops in C (the convertor, the coll algorithm
library); the TPU analog of "hand-tuned native hot path" is a pallas
kernel feeding the MXU directly from VMEM.  A kernel compiles for the TPU
or fails: nothing here chooses interpret mode or another path on its own.
Callers that want the pure-XLA form ask for it by name
(``parallel.attention.local_impl``).

Which form a program takes is its model's rule from static facts, never a
kernel's: a kernel says what it tiles (``tiles``, ``block``) and refuses the
rest.  The latent mixer (``models/mla.py``) takes ``latent_attention`` for a
prefill from the length where it was measured to win (``mla.KERNEL_FROM``)
and ``latent_decode`` for a cached step over a cache of whole blocks of 1024
positions, both only in a trace for TPUs; the ``jax.numpy`` forms anywhere
else.  Power retention (``models/retention.py``) takes ``retention_prefill``
for a decoder's prefill of up to ``retention.CROSSOVER`` positions
(``retention.direct``: forward only, whole tiles) with
``retention_end_state`` for the state that prefill leaves, and
``retention_update`` for a cached step over a float32 state of heads 128 wide
(``retention_update.block``), again only in a trace for TPUs; its trainer and
every other length keep the chunked ``jax.numpy`` form.
"""

from ompi_tpu.ops.flash_attention import flash_attention, flash_attention_lse
from ompi_tpu.ops.grouped_matmul import grouped_matmul

__all__ = ["flash_attention", "flash_attention_lse", "grouped_matmul"]
