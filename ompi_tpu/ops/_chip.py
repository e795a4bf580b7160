"""What the choice of a kernel asks of the chip, in one place: whether what
is being traced will run on TPUs, and the VMEM a kernel gets unasked.

This module imports nothing of pallas (``ops/_pallas.py``'s import *is* that
import, most of a second of a program's set-up): a mixer asks the question
before it imports a kernel, and a cell that takes none never pays for one.

Callers reach the question through the module at call time
(``_chip._traced_for_tpus()``), never through a name bound at import, so a
test that tells the program it is on TPUs patches this one attribute and
every choosing site follows.
"""

from __future__ import annotations

__all__ = ["_traced_for_tpus", "_VMEM_BUDGET_BYTES"]

# What a kernel's blocks may take of VMEM where its call names no limit: what
# Mosaic gives such a kernel (16 MiB on the v5e's compiler; what a rule admits
# just under it compiles, tests/parallel/test_grouped_matmul_compiled.py).
# The calls name none on purpose: a stated ``vmem_limit_bytes`` is reserved
# whole, whatever the kernel needs, and XLA then assigns less VMEM to the
# operations around it: at 32 MiB Keye-VL's cached step, whose calls take the
# same blocks either way, ran 0.9% slower (PERF.md section 6, PR 46).  A
# kernel that needs more than this says so itself, with its own reason
# (``ssm_scan``, ``flash_attention``, ``latent_attention``).
_VMEM_BUDGET_BYTES = 16 << 20


def _traced_for_tpus() -> bool:
    """Whether what is being traced will run on TPUs: the kind of the
    devices of the mesh the trace is under (a decoder's and a trainer's
    programs are ``shard_map``s over theirs), attached or described for a
    compile; under no mesh, the process's own backend."""
    import jax

    device = jax.sharding.get_abstract_mesh().abstract_device
    if device is None:
        return jax.default_backend() == "tpu"
    return device.device_kind.startswith("TPU")
