"""Tensor-parallel building blocks (Megatron-style column/row sharding).

The matmul-sharding recipe of the scaling playbook: a column-parallel matmul
keeps its activation sharded over ``tp`` (no comm), the following
row-parallel matmul contracts the sharded dimension and finishes with one
``psum`` over ``tp`` — one allreduce per MLP/attention block, riding ICI.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["column_parallel", "row_parallel", "SUMMED_NAME"]

# ``jax.ad_checkpoint.checkpoint_name`` tag of a row-parallel product's sum
# where its passes are staged (``row_parallel(by_halves=True)``): a remat
# policy that keeps it makes no psum again in the backward pass.
SUMMED_NAME = "row_parallel_sum"


def column_parallel(x, w_shard):
    """x: (..., D) replicated over tp; w_shard: (D, F/tp) local shard.
    Returns (..., F/tp) — output stays tp-sharded, no communication."""
    import jax.numpy as jnp

    return jnp.einsum("...d,df->...f", x, w_shard)


def _product(x_shard, w_shard):
    import jax.numpy as jnp

    return jnp.einsum("...f,fd->...d", x_shard, w_shard)


def row_parallel(x_shard, w_shard, comm, axis: Optional[str] = None,
                 by_halves: bool = False):
    """x_shard: (..., F/tp); w_shard: (F/tp, D).  Contracts the sharded
    dimension and psums partial products over tp → replicated (..., D).

    ``by_halves`` (the train step's, which takes the gradient inside its
    shard_map, where a psum's transpose is the psum of the cotangent): the
    product and its sum are made in two halves of the leading axis, the
    sequences, in the forward pass and in the backward, and a half's sum
    runs under the other half's product (:func:`_summed_by_halves`).
    Without it, on a ``tp`` of one device or with an odd leading axis, one
    product, one psum, and JAX's own transpose: a psum with nothing beside
    it."""
    from jax import lax

    from ompi_tpu.core.scopes import coll

    ax = axis or comm.axes[-1]
    if int(comm.mesh.shape[ax]) == 1:
        # degenerate tp: psum is identity, skip the channel op
        return _product(x_shard, w_shard)
    if by_halves and x_shard.ndim > 1 and x_shard.shape[0] % 2 == 0:
        return _summed_by_halves(x_shard, w_shard, ax)
    partial = _product(x_shard, w_shard)
    with coll("allreduce", ax):
        return lax.psum(partial, ax)


def _summed_by_halves(x_shard, w_shard, ax: str):
    """``psum(x_shard @ w_shard, ax)`` with both passes staged.

    Every product of a block that follows this sum waits for it, forward
    (the next norm) and backward (the cotangent of the sum is summed over
    ``ax`` again: inside a shard_map without replication checks that is the
    psum's transpose, and the block's backward starts there).  Left whole,
    the all-reduce has no independent matmul beside it and is all on the
    critical path.  Here each pass sums one half of the sequences, then
    starts the other half's sum with a product that does not wait for it
    beside it: in the forward the first half's sum beside the second
    half's product, in the backward the second half's sum beside the first
    half's product with the weight.  The two are handed on through one
    ``optimization_barrier``, so the TPU's compiler makes that all-reduce
    an asynchronous pair around the matmul
    (``transformer._OVERLAP_OPTIONS``), and the backward's second operand
    waits for the first sum, or the two, ready together, would be combined
    into one all-reduce, which is not made asynchronous.  The sums, the
    products and their operands are those of the whole, a half of the rows
    at a time; the weight's gradient is one product, of both halves.

    The forward's sum is tagged ``SUMMED_NAME``: kept by the layer's remat
    policy, the backward pass makes no psum again, which the compiler would
    combine with a half's."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.ad_checkpoint import checkpoint_name

    from ompi_tpu.core.scopes import coll

    def forward(x, w):
        x_a, x_b = jnp.split(x, 2)
        p_a = _product(x_a, w)
        with coll("allreduce", ax):
            s_a = lax.psum(p_a, ax)
        s_a, p_b = lax.optimization_barrier((s_a, _product(x_b, w)))
        with coll("allreduce", ax):
            s_b = lax.psum(p_b, ax)
        return checkpoint_name(jnp.concatenate([s_a, s_b]), SUMMED_NAME)

    def backward(operands, ct):
        x, w = operands
        half = jax.ShapeDtypeStruct((x.shape[0] // 2, *x.shape[1:]), x.dtype)
        to_x = jax.linear_transpose(lambda x: _product(x, w), half)
        to_w = jax.linear_transpose(lambda w: _product(x, w), w)
        ct_a, ct_b = jnp.split(ct, 2)
        with coll("allreduce", ax):
            g_a = lax.psum(ct_a, ax)
        ct_b, g_a = lax.optimization_barrier((ct_b, g_a))
        with coll("allreduce", ax):
            g_b = lax.psum(ct_b, ax)
        g_b, dx_a = lax.optimization_barrier((g_b, *to_x(g_a)))
        (dx_b,), (dw,) = to_x(g_b), to_w(jnp.concatenate([g_a, g_b]))
        return jnp.concatenate([dx_a, dx_b]), dw

    summed = jax.custom_vjp(forward)
    summed.defvjp(lambda x, w: (forward(x, w), (x, w)), backward)
    return summed(x_shard, w_shard)
