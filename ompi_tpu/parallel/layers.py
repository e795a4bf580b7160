"""Tensor-parallel building blocks (Megatron-style column/row sharding).

The matmul-sharding recipe of the scaling playbook: a column-parallel matmul
keeps its activation sharded over ``tp`` (no comm), the following
row-parallel matmul contracts the sharded dimension and finishes with one
``psum`` over ``tp`` — one allreduce per MLP/attention block, riding ICI.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["column_parallel", "row_parallel"]


def column_parallel(x, w_shard):
    """x: (..., D) replicated over tp; w_shard: (D, F/tp) local shard.
    Returns (..., F/tp) — output stays tp-sharded, no communication."""
    import jax.numpy as jnp

    return jnp.einsum("...d,df->...f", x, w_shard)


def row_parallel(x_shard, w_shard, comm, axis: Optional[str] = None):
    """x_shard: (..., F/tp); w_shard: (F/tp, D).  Contracts the sharded
    dimension and psums partial products over tp → replicated (..., D)."""
    import jax.numpy as jnp
    from jax import lax

    from ompi_tpu.core.scopes import coll

    partial = jnp.einsum("...f,fd->...d", x_shard, w_shard)
    ax = axis or comm.axes[-1]
    if int(comm.mesh.shape[ax]) == 1:
        return partial  # degenerate tp: psum is identity, skip the channel op
    with coll("allreduce", ax):
        return lax.psum(partial, ax)
