"""Rotary embedding as a pallas kernel over (B, T, H·D), in place of layout.

The jnp form (``models/transformer._rope``) slices a head into halves of
D/2 lanes.  For D = 128 those are half lane tiles: compiled for a v5e
beside the flash kernels, which read q and k row-major as (B, T, H·D), the
halves are copied out as (B, T, H, 64) arrays and put back together by a
padding fusion, a layer and a pass.  A train step of cell 1's model takes
408.9 ms with the jnp form under the kernels and 369.3 ms with this one
(PERF.md section 6, PR 28, chip call 26).  Here a block is rows of whole
heads: each head's (rows, D) tile is rolled by D/2 lanes in VMEM, which
brings every element's partner (the other half of its head) beside it, and
the sums are the jnp form's, term for term:

    out = x · [cos, cos] + roll(x, D/2) · [−sin, sin]

One read and one write of x.  The backward is the same kernel with the sine
negated (the roll is its own transpose and swaps the sign of [−sin, sin]).

Like the other kernels here it always compiles for the TPU; ``rope_tiles``
says which shapes it takes, and the model calls it on a mesh of TPUs only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["rope", "rope_tiles"]

# a block of x (rows × H·D) may take this many bytes of VMEM; in and out are
# double buffered
_BLOCK_BYTES = 2 << 20


def _rows(t: int, width: int, itemsize: int) -> int:
    """Rows a block: the largest power of two from 512 down to 16 that
    divides ``t`` and keeps the block under ``_BLOCK_BYTES``; 0 if none."""
    rows = 512
    while rows >= 16:
        if t % rows == 0 and rows * width * itemsize <= _BLOCK_BYTES:
            return rows
        rows //= 2
    return 0


def rope_tiles(t: int, heads: int, head_dim: int, dtype) -> bool:
    """True when :func:`rope` takes (·, t, heads·head_dim) of ``dtype``:
    heads of whole lane tiles, and rows that tile."""
    return (head_dim % 128 == 0
            and _rows(t, heads * head_dim, jnp.dtype(dtype).itemsize) > 0)


def _kernel(x_ref, cos_ref, sin_ref, o_ref, *, head_dim: int):
    from ompi_tpu.ops._pallas import pltpu

    cos, sin = cos_ref[...], sin_ref[...]                   # (rows, D) f32
    for h in range(x_ref.shape[-1] // head_dim):
        lanes = slice(h * head_dim, (h + 1) * head_dim)
        x = x_ref[0, :, lanes].astype(jnp.float32)
        partner = pltpu.roll(x, head_dim // 2, 1)
        o_ref[0, :, lanes] = (x * cos + partner * sin).astype(o_ref.dtype)


# jitted: q and k, forward, recomputation and backward are six call sites of a
# train step at one shape, traced once a process and lowered once a program
@functools.partial(jax.jit, static_argnums=3)
def _call(x, cos, sin, head_dim: int):
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    b, t, width = x.shape
    rows = _rows(t, width, x.dtype.itemsize)
    return pallas_call(
        functools.partial(_kernel, head_dim=head_dim),
        grid=(b, t // rows),
        in_specs=[pl.BlockSpec((1, rows, width), lambda i, j: (i, j, 0)),
                  pl.BlockSpec((rows, head_dim), lambda i, j: (j, 0)),
                  pl.BlockSpec((rows, head_dim), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((1, rows, width), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="rope",
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rope(x, cos, sin, head_dim):
    return _call(x, cos, sin, head_dim)


def _rope_fwd(x, cos, sin, head_dim):
    return _call(x, cos, sin, head_dim), (cos, sin)


def _rope_bwd(head_dim, res, g):
    cos, sin = res
    return _call(g, cos, -sin, head_dim), None, None


_rope.defvjp(_rope_fwd, _rope_bwd)


def rope(x, cos, sin):
    """x (B, T, H, D) rotated by the angles whose cosines and sines are
    ``cos``, ``sin`` (T, D/2) float32: what ``x1·cos − x2·sin, x1·sin +
    x2·cos`` over the halves x1, x2 of each head gives, in x's dtype.  No
    gradient flows to the angles."""
    b, t, h, d = x.shape
    if not rope_tiles(t, h, d, x.dtype):
        raise ValueError(f"rope: no block of rows tiles {x.shape} "
                         f"{x.dtype}; use the jnp form")
    cos = jnp.concatenate([cos, cos], axis=-1).astype(jnp.float32)
    sin = jnp.concatenate([-sin, sin], axis=-1).astype(jnp.float32)
    return _rope(x.reshape(b, t, h * d), cos, sin, d).reshape(b, t, h, d)
