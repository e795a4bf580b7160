"""Mamba-2's cached step as one pallas pass over a layer's carried states:
``models/ssm._mix``'s carried branch on a TPU.

A layer carries a ``(P, N)`` matrix, head width by state depth, for every
(sequence, head), in two bytes an element: 335.5 MB at granite-4.0-h-small's
160 x 128 x 64 x 128 in bfloat16.  One new position decays it under one
outer product and reads it through the position's ``C``:

    h' = exp(dt a) h + (dt x) B^T;      y = h' C;      h <- round(h')

As ``jax.numpy`` the compiler makes two fusions of this, each of which takes
the state as a parameter and recomputes the float32 update: the one that
writes the rounded state and one that reads the old state again for ``y``,
three passes where a read and a write are needed (``ROADMAP.md`` S19.1).
Here a block of one sequence's heads is copied into VMEM, swept once there
and copied back *into the buffer it came from* (``input_output_aliases``):
the state crosses the HBM once each way, and no second copy of a layer's
state exists.

Layout.  In a ``(P, N)`` tile the head's width lies on sublanes and the
state's depth on lanes, sixteen rows a bfloat16 tile.  ``B`` and ``C`` run
along the depth and are rows, one a sequence and group; they arrive group
by group with the batch on sublanes, eight sequences a block, as the mixer's
convolution leaves them (as ``(B, G, N)`` a single group's operand had the
shape of the mixer's own ``(B, 1, F)`` arrays, and the layout the kernel
asks of it, one-row tiles, went to all of them: three fusions a layer ten
times slower, 1.7 ms a step on the chip, PR 68).  ``dt x`` multiplies along
the width, so the kernel needs it as columns; it arrives as rows, ``(heads,
P)`` with P on lanes, beside a sublane tile of what is one number a head
(the decay, ``B . C``), and both are transposed in VMEM, a block's at once
(handing them over as columns would pad them to 128 lanes in the HBM, more
bytes than the state).

``y`` is the unrounded update's product with ``C``, as the ``jax.numpy``
form's, and no lane of it is summed on the vector unit.  The update is
linear in the old state, so

    y = exp(dt a) (h C) + (dt x) (B . C)

and ``h C`` is a product of the state *as it is stored*, two bytes an
element, which the matrix unit multiplies exactly and adds up in float32:
its left operand is ``C`` in bfloat16, a product a piece of up to three
that add up to it (one where ``C`` is bfloat16 already: a float32 ``C`` is
their sum to its last bit), a head's tile is the transposed right operand,
and what comes out is a row with the head's width on lanes, as ``y`` is
stored.  The vector unit is left with the update alone: widen, two
products, one sum, round.  (On the chip, a call at cell 13's sizes: 1.047
ms beside 1.046 of a kernel that only copies the same blocks; with ``y``
summed over lanes by the XLU 2.26 ms, by rolls and adds 12.1: ``PERF.md``
section 6, PR 68.)

No backward pass (a decoder's step has none).  Like the other kernels here
it always compiles for the TPU; :func:`block` says where a caller takes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ompi_tpu.ops._chip import _VMEM_BUDGET_BYTES

__all__ = ["ssm_update", "block"]

# The state's depth: one tile of lanes, the shape the kernel is swept on.
_LANES = 128
# Rows of the numbers that are one a head (the decay, ``B . C``, zeros), of
# the product's left operand (a bfloat16 sublane tile), and sequences a block
# of ``B`` and ``C`` (a float32 sublane tile).
_A_HEAD, _C_ROWS, _SEQUENCES = 8, 16, 8


def _working_set_bytes(heads: int, P: int, groups: int = 1) -> int:
    """VMEM the kernel holds at a block of ``heads`` states: the state in
    and the state out twice each (the pipeline copies the next block while
    this one is swept), and likewise ``dt x`` in and ``y`` out (rows of P
    padded to whole lane tiles), the numbers a head, and eight sequences'
    ``B`` and ``C``; the two transposed and the decays' rows once."""
    lanes = -(-P // _LANES) * _LANES
    return (2 * (2 * 2 * heads * P * _LANES + 2 * 4 * heads * lanes
                 + 4 * _A_HEAD * heads + 2 * 4 * _SEQUENCES * groups * _LANES)
            + 4 * (lanes + 2 * _LANES) * heads)


def block(tpu: bool, dtype, heads: int, P: int, N: int, groups: int = 1):
    """The block ``(1, heads, P, N)`` of a ``(B, heads, P, N)`` state of
    ``dtype`` that :func:`ssm_update` streams, a sequence's heads, or None
    where the ``jax.numpy`` form runs: off a mesh of TPUs (``tpu``:
    attached, or described for a compile; the kernel compiles for nothing
    else), for a state that is not bfloat16 (the matrix unit's exact
    operand), for a depth that is not one tile of 128 lanes, for a head's
    width that is not whole bfloat16 sublane tiles of sixteen, and where a
    sequence's heads do not fit the kernel's VMEM budget twice over each
    way.  All static: a program's steps take the kernel in every layer or in
    none."""
    if (not tpu or jnp.dtype(dtype) != jnp.bfloat16 or N != _LANES or P % 16
            or heads % groups
            or _working_set_bytes(heads, P, groups) > _VMEM_BUDGET_BYTES):
        return None
    return 1, heads, P, N


def _kernel(layer_ref, x_ref, a_head_ref, b_ref, c_ref, s_ref, y_ref, s_out,
            decay, *, pieces):
    from ompi_tpu.ops._pallas import pl

    heads, groups = x_ref.shape[0], b_ref.shape[0]
    f32 = jnp.float32
    x = x_ref[...]                                          # (heads, P)
    # what multiplies along a tile's sublanes, as columns: ``dt x`` (P,
    # heads), and the numbers a head (heads, 8): the decay, ``B . C``
    x_c, a_head = x.T, a_head_ref[...].T
    # a head's decay along its row's lanes, in scratch: a (1, 1) of a value
    # does not broadcast over a tile, a row that is loaded does
    decay[...] = jnp.broadcast_to(a_head[:, 0:1], decay.shape)
    # this sequence's row of the eight in ``B``'s and ``C``'s blocks
    row = pl.ds(pl.program_id(0) % _SEQUENCES, 1)
    for g in range(groups):
        b = b_ref[g, row, :]                                # (1, N)
        # C as the product's left operand: bfloat16 pieces that add up to
        # it, each a row over a sublane tile
        rest, c = c_ref[g, row, :], []
        for _ in range(pieces):
            piece = rest.astype(jnp.bfloat16).astype(f32)
            c.append(jnp.broadcast_to(piece, (_C_ROWS, _LANES)).astype(
                jnp.bfloat16))
            rest = rest - piece
        # unrolled: a loop's sweeps do not overlap (``retention_update.py``)
        for h in range(g * heads // groups, (g + 1) * heads // groups):
            at = slice(h, h + 1)
            S = s_ref[h]                                    # (P, N), stored
            s_out[h] = (S.astype(f32) * decay[at, :]
                        + x_c[:, at] * b).astype(s_out.dtype)
            # C by the stored state, transposed: exact products that add up
            # in float32, a row (1, P) of each piece
            y_ref[at, :] = sum(jax.lax.dot_general(
                piece, S, (((1,), (1,)), ((), ())),
                preferred_element_type=f32)[0:1] for piece in c)
    y_ref[...] = y_ref[...] * a_head[:, 0:1] + x * a_head[:, 1:2]


@functools.partial(jax.jit, static_argnames="pieces")
def _call(stack, layer, x, a_head, b, c, *, pieces):
    from ompi_tpu.ops._pallas import pallas_call, pl, pltpu

    _, B, H, P, N = stack.shape
    rows = pl.BlockSpec((None, H, P), lambda i, layer: (i, 0, 0))
    # eight sequences' rows of B and C a block: a sequence's own would be a
    # block of one sublane
    a_sequence = pl.BlockSpec((b.shape[0], _SEQUENCES, N),
                              lambda i, layer: (0, i // _SEQUENCES, 0))
    state = pl.BlockSpec((None, None, H, P, N),
                         lambda i, layer: (layer[0], i, 0, 0, 0))
    y, stack = pallas_call(
        functools.partial(_kernel, pieces=pieces),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[rows,
                      pl.BlockSpec((None, _A_HEAD, H),
                                   lambda i, layer: (i, 0, 0)),
                      a_sequence, a_sequence, state],
            out_specs=(rows, state),
            scratch_shapes=[pltpu.VMEM((H, _LANES), jnp.float32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((B, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(stack.shape, stack.dtype)),
        # operands are counted with the prefetched scalar
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="ssm_update",
    )(layer, x, a_head, b, c, stack)
    return y, stack


def ssm_update(state, x, dt, a, b, c, layer=None):
    """One new position against a layer's carried states: ``state`` (B, H,
    P, N) bfloat16, or the stack (L, B, H, P, N) of which ``layer`` (a
    traced int32) is that layer; x (B, H, P) the position's input, dt (B, H)
    float32, positive, a (H,) float32, negative, b and c (B, G, N), head
    ``h`` reading group ``h // (H / G)``.  Returns y (B, H, P) float32, the
    unrounded update's product with ``c``, and the state (the stack with
    that layer) ``exp(dt a) h + (dt x) b^T`` rounded to its type, written
    into ``state``'s buffer where the caller donates it; a stack's other
    layers are not touched."""
    f32 = jnp.float32
    stack = state[None] if layer is None else state
    _, B, H, P, N = stack.shape
    G = b.shape[1]
    if block(True, stack.dtype, H, P, N, G) is None:
        raise ValueError(
            f"ssm_update: a {stack.dtype} state {stack.shape[1:]} under {G} "
            f"groups does not tile (bfloat16, a depth of {_LANES}, a head's "
            f"width in whole sixteens, a sequence's heads within "
            f"{_VMEM_BUDGET_BYTES >> 20} MiB of VMEM)")
    dt, b32, c32 = dt.astype(f32), b.astype(f32), c.astype(f32)
    # one number a head: the decay, and ``B . C`` of the head's group
    a_head = jnp.stack([jnp.exp(dt * a.astype(f32)), jnp.repeat(
        jnp.sum(b32 * c32, axis=-1), H // G, axis=1)], axis=1)
    y, stack = _call(
        stack, jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1),
        x.astype(f32) * dt[..., None],
        jnp.pad(a_head, ((0, 0), (0, _A_HEAD - 2), (0, 0))),
        # B and C with the batch on sublanes, a group's rows together: as
        # the mixer's convolution leaves them, and not a tile a sequence
        jnp.moveaxis(b32, 1, 0), jnp.moveaxis(c32, 1, 0),
        # a bfloat16 C is one piece; a float32 C the sum of three
        pieces=1 if c.dtype == jnp.bfloat16 else 3)
    return y, (stack[0] if layer is None else stack)
