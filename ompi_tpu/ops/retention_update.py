"""Power retention's cached step as one pallas pass over a layer of the
stacked matrix states: ``models/retention.core`` on a TPU.

A decoder carries a ``(D, d)`` float32 matrix, ``phi`` of a key by value,
for every (layer, sequence, K/V head), all layers in one stack: 1.64 GB a
layer at Brumby's 48 x 8 x 8320 x 128.  One new position reads the old
state for the query heads of its K/V head and decays it under one outer
product:

    sums_r = S^T phi(q_r)   r = 0 .. R-1;      S_new = g S + phi(k) v^T

As ``jax.numpy`` the compiler makes two computations of this, a float32
product that reads the layer and a fusion that reads it again and writes
it: three passes where a read and a write are needed (``ROADMAP.md``
S14.1).  Here a block of one (sequence, K/V head)'s rows is copied into
VMEM, swept once there (the write does not wait for the read: both are of
the old state) and copied back *into the place in the stack it came from*:
the stack is the aliased operand (``input_output_aliases``), the layer a
prefetched scalar that the index maps read, so one layer's blocks are
visited and every other layer's bytes stay as they lie.  A whole state is
4.26 MB and does not fit VMEM twice each way, so the state's axis is the
grid's last, and the sums add up across it in the result's block.

Layout.  In a block the state's axis lies on sublanes and the value axis
on lanes.  ``phi(q)`` and ``phi(k)`` multiply along the state's axis, so the
kernel needs them as columns; they arrive as rows, as ``retention.phi``
leaves them (a ``(D, 1)`` or ``(D, R)`` operand would be padded to 128 lanes
in the HBM, as many bytes as the state), and are put together in VMEM as a
sublane tile of eight ``(8, rows)``: the query heads', then the key's, then
zeros.  As rows they are the
left operand of the read as it is, a float32 product on the matrix unit
(``Precision.HIGHEST``, as the ``jax.numpy`` form's), 128 rows of the state
a product; for the write the tile is transposed in VMEM and the key's
column taken.  ``v`` and the decay run along the values and are rows; the
decay and the outer product are the vector unit's.  (The sums on the vector
unit, a lane broadcast a head and register of state, took 6.8 ms a layer
where this form takes the 5.6 of a kernel that only copies: PR 50, on the
chip.)

Everything is float32, as the configuration's ``retention_state_dtype``
says.  No backward pass (a decoder's step has none).  Like the other
kernels here it always compiles for the TPU; :func:`block` says where a
caller takes it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.ops._chip import _VMEM_BUDGET_BYTES

__all__ = ["retention_update", "block"]

# Rows of the state a sweep in VMEM takes at a time: 16 registers of state,
# and of the columns a tile of 128 lanes, eight of them a sublane tile.
_SWEEP = 128


def _columns(heads: int) -> int:
    """Rows of a tile of the columns: ``heads`` query heads' and the key's,
    in whole sublane tiles of eight."""
    return -(-(heads + 1) // 8) * 8


def _working_set_bytes(rows: int, d: int, heads: int = 1) -> int:
    """VMEM the kernel holds at a block of ``rows`` of the state's axis
    under ``heads`` query heads a K/V head: the state in and the state out
    twice each (the pipeline copies the next block while this one is swept),
    the query heads' and a sequence's keys' vectors in twice (a sublane tile
    each) and their tile once, and the two rows and the sums twice."""
    columns = _columns(heads)
    return 4 * (4 * rows * d + (3 * columns + 16) * rows
                + 2 * (16 + columns) * d)


def block(tpu: bool, dtype, D: int, d: int):
    """The block ``(rows, d)`` of a (sequence, K/V head)'s ``(D, d)`` state
    of ``dtype`` that :func:`retention_update` streams, or None where the
    ``jax.numpy`` form runs: off a mesh of TPUs (``tpu``: attached, or
    described for a compile; the kernel compiles for nothing else), for a
    state that is not float32, for a head that is not whole tiles of 128
    lanes, and for a state's axis that is not whole sweeps of 128 rows.  The
    most rows that fit the kernel's VMEM budget (under up to seven query
    heads a K/V head; eight more are 3% more), whole sweeps and a divisor of
    ``D``.  All static: a program's steps take the kernel in every layer or
    in none."""
    if (not tpu or jnp.dtype(dtype) != jnp.float32 or d % 128
            or D % _SWEEP):
        return None
    sweeps = D // _SWEEP
    rows = next(n * _SWEEP for n in range(sweeps, 0, -1) if sweeps % n == 0
                and _working_set_bytes(n * _SWEEP, d) <= _VMEM_BUDGET_BYTES)
    return rows, d


def _kernel(layer_ref, pq_ref, pk_ref, v_ref, decay_ref, s_ref, sums_ref,
            s_out, cols):
    from ompi_tpu.ops._pallas import pl

    heads, rows = pq_ref.shape
    v, decay = v_ref[...], decay_ref[...]                   # (1, d)
    # the vectors along the state's axis, a whole sublane tile of them: the
    # query heads', this K/V head's key's, zeros
    cols[...] = jnp.zeros_like(cols)
    cols[:heads, :] = pq_ref[...]
    cols[heads:heads + 1, :] = pk_ref[pl.ds(pl.program_id(1), 1), :]
    sums = jnp.zeros(sums_ref.shape, jnp.float32)
    # unrolled: as a loop the sweeps do not overlap, and a block took 8.7 ms
    # a layer for the copies' 5.6 (PR 50, on the chip)
    for i in range(rows // _SWEEP):
        at = slice(i * _SWEEP, (i + 1) * _SWEEP)
        S, tile = s_ref[at, :], cols[:, at]                 # (128, d), (8, 128)
        s_out[at, :] = S * decay + tile.T[:, heads:heads + 1] * v
        sums += jnp.dot(tile, S, precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    sums_ref[...] += sums       # the key's row too, which nothing reads


@jax.jit
def _call(stack, layer, pq, decay, pk, v):
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    _, B, G, D, d = stack.shape
    R = pq.shape[2]
    columns = _columns(R)
    rows = block(True, stack.dtype, D, d)[0]
    row = pl.BlockSpec((None, None, 1, d), lambda b, g, j, layer: (b, g, 0, 0))
    state = pl.BlockSpec((None, None, None, rows, d),
                         lambda b, g, j, layer: (layer[0], b, g, j, 0))
    sums, stack = pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, G, D // rows),
            in_specs=[
                pl.BlockSpec((None, None, R, rows),
                             lambda b, g, j, layer: (b, g, 0, j)),
                # a sequence's keys, every K/V head's: a row of its own a
                # head would be padded to eight in the HBM
                pl.BlockSpec((None, G, rows), lambda b, g, j, layer: (b, 0, j)),
                row, row, state],
            out_specs=(pl.BlockSpec((None, None, columns, d),
                                    lambda b, g, j, layer: (b, g, 0, 0)),
                       state),
            scratch_shapes=[pltpu.VMEM((columns, rows), jnp.float32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((B, G, columns, d), jnp.float32),
                   jax.ShapeDtypeStruct(stack.shape, stack.dtype)),
        # operands are counted with the prefetched scalar
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="retention_update",
    )(layer, pq, pk, v[:, :, None], jnp.broadcast_to(
        decay[:, :, None, None], (B, G, 1, d)), stack)
    return sums[:, :, :R], stack


def retention_update(stack, layer, pq, decay, pk, v):
    """One new position against layer ``layer`` (a traced int32) of the
    carried matrix states ``stack`` (L, B, G, D, d) float32, ``phi`` of a key
    by value: pq (B, G, R, D), the feature maps of the R query heads of each
    K/V head; decay (B, G), pk (B, G, D) and v (B, G, d), what the position
    writes.  Returns what the query heads read of the state as it was,
    ``S^T pq`` (B, G, R, d) float32, and the stack with that layer ``decay S
    + pk v^T``, written into ``stack``'s buffer where the caller donates
    it; the other layers are not touched."""
    _, _, _, D, d = stack.shape
    took = block(True, stack.dtype, D, d)
    if took is None or _working_set_bytes(
            took[0], d, pq.shape[2]) > _VMEM_BUDGET_BYTES:
        raise ValueError(
            f"retention_update: a {stack.dtype} stack {stack.shape} under "
            f"{pq.shape[2]} query heads a K/V head does not tile (float32, "
            f"heads 128 lanes or a multiple wide, a state's axis of whole "
            f"{_SWEEP}s, a block within {_VMEM_BUDGET_BYTES >> 20} MiB of "
            f"VMEM)")
    f32 = jnp.float32
    return _call(stack, jnp.asarray(layer, jnp.int32).reshape(1),
                 *(y.astype(f32) for y in (pq, decay, pk, v)))
