"""Power retention's state after the last position of a whole prompt, formed
once: ``models/retention.py::end_state``'s sums as one pallas kernel, beside
``ops/retention_prefill.py`` in a decoder's prefill on TPUs.

With ``w_j`` the decay from position ``j`` to the sequence's end, a K/V head:

    S = sum_j w_j phi(k_j) v_j^T        (D, d)
    z = sum_j w_j phi(k_j)              (D,)

``phi(k)`` is ``D = (d/2 + 1) d`` wide, 8320 for a head of 128, and float32.
As ``jax.numpy`` a chunk of it is written out (136 MB for 256 positions of 2
sequences and 8 K/V heads) with ``z``'s sum and read back under the decay
for the product with ``v``: 1.09 GB each way a pass and layer of cell 8 for
70 GFLOP whose operands are 16.8 MB, and XLA keeps it so whatever the
chunk (PR 63).  Here a block's ``phi(k)`` never leaves the chip: a head's
``S`` is 4.26 MB of float32 and stays in VMEM while the head's keys and
values stream past it.

A grid cell is (sequence, K/V head) with the blocks of ``ROWS`` positions
as the last, sequential, axis.  Keys and values are read as they lie,
positions major, a K/V head one lane block of ``(B, T, G d)``; the decays
come as a column a head, computed outside (``retention._decays_to_end``:
every exponent at most zero, none the difference of two sums over the
sequence).  A block's ``phi(k)`` is built a shift at a time in
``retention.phi``'s layout, ``k`` turned by ``s`` lanes times ``k`` times
the shift's constants, float32, then times ``w`` a row; ``z`` adds its
column sums in float32 before any cast; the product with ``v`` takes it in
the operands' type with float32 sums, as ``end_state``'s ``pk.astype(cdt)``.
With ``ROWS`` the configuration's chunk the sums run in ``end_state``'s
order.

The product a shift is ``v^T . phi_s`` (d x ROWS by ROWS x d), so what
accumulates is ``S``'s transpose a shift, turned once behind the last block:
on a v5e in a loop at cell 8's sizes 0.83 ms a pass and layer where
``phi_s^T . v`` straight into ``S`` took 0.92, ``phi(k)^T`` built with the
positions on the lanes 0.88, a ``fori_loop`` over the shifts with a dynamic
turn 3.85, and ``end_state`` 3.49 (``PERF.md`` section 6, PR 73).  The 65
shifts are unrolled for that.  The call names no ``vmem_limit_bytes``: the
head's ``S`` twice (the output's two buffers) and its transpose are 12.8 MB
of what Mosaic gives unasked (16 MiB on the v5e).

**``phi``'s constants are an operand** (:func:`retention_end_state`'s
``c``: ``retention.phi`` of a vector of ones, a row of ``d`` a shift), so a
``phi`` that a caller has wrapped reaches the state through here as it does
through ``end_state``.  Forward only: a trainer keeps ``retention.chunked``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["retention_end_state", "tiles", "ROWS"]

# positions a block: ``Retention.chunk``'s default, so that the kernel adds
# up in ``end_state``'s order; a block's float32 keys are half the vector
# registers (128 positions took 1.03 ms for 0.92, 512 moved nothing)
ROWS = 256
_TN = (((0,), (0,)), ((), ()))      # a^T . b


def tiles(t: int, d: int) -> bool:
    """True where the kernel takes sequences of ``t`` positions of heads
    ``d`` wide: a head one block of 128 lanes, whole blocks of ``ROWS``
    positions (nothing is padded here)."""
    return d == 128 and t > 0 and t % ROWS == 0


def _kernel(c_ref, k_ref, v_ref, w_ref, S_ref, z_ref, St_ref, zs_ref):
    """One block of positions of a (sequence, K/V head): every shift's rows
    of ``phi(k) w`` added into the head's ``S^T`` and ``z``, which leave
    behind the last block."""
    from jax import lax

    from ompi_tpu.ops._pallas import pl, pltpu

    f32 = jnp.float32
    rows, d = k_ref.shape[1:]
    shifts = c_ref.shape[0]
    block = pl.program_id(2)

    @pl.when(block == 0)
    def _():
        St_ref[...] = jnp.zeros_like(St_ref)
        zs_ref[...] = jnp.zeros_like(zs_ref)

    k = k_ref[0].astype(f32)                        # (rows, d)
    v = v_ref[0]
    w = w_ref[...]                                  # (rows, 1)
    for s in range(shifts):
        # k[(a + s) % d] at lane a, as ``retention._turns`` lays a shift
        turned = pltpu.roll(k, d - s, 1) if s else k
        pk = turned * k * c_ref[s:s + 1, :] * w
        # eight partial sums a lane: the vector unit's adds, no reduce
        zs_ref[s] += pk.reshape(rows // 8, 8, d).sum(axis=0)
        St_ref[s] += lax.dot_general(v, pk.astype(v.dtype), _TN,
                                     preferred_element_type=f32)

    @pl.when(block == pl.num_programs(2) - 1)
    def _():
        for s in range(shifts):
            S_ref[s * d:(s + 1) * d, :] = St_ref[s].T
        z_ref[...] = zs_ref[...].sum(axis=1)


@jax.jit
def _call(c, k3, v3, w):
    """c (shifts, d) float32; k3, v3 (B, T, G d); w (B, T, G) float32 -> S
    (B, G, D, d) and z (B, G, shifts, d) float32; T whole blocks."""
    from ompi_tpu.ops._pallas import pallas_call, pl, pltpu

    f32 = jnp.float32
    b, t, groups = w.shape
    shifts, d = c.shape
    head = pl.BlockSpec((1, ROWS, d), lambda b, g, i: (b, i, g))
    return pallas_call(
        _kernel,
        grid=(b, groups, t // ROWS),
        in_specs=[
            pl.BlockSpec((shifts, d), lambda b, g, i: (0, 0)),
            head, head,
            # down the sublanes: an operand one lane wide, as the prefill's
            pl.BlockSpec((None, None, ROWS, 1), lambda b, g, i: (b, g, i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, None, shifts * d, d),
                         lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((None, None, shifts, d),
                         lambda b, g, i: (b, g, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((b, groups, shifts * d, d), f32),
                   jax.ShapeDtypeStruct((b, groups, shifts, d), f32)),
        scratch_shapes=[pltpu.VMEM((shifts, d, d), f32),
                        pltpu.VMEM((shifts, 8, d), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="retention_end_state",
    )(c, k3, v3, jnp.moveaxis(w, 1, 2)[..., None])


def retention_end_state(k, v, w, c):
    """``S`` (B, G, D, d) and ``z`` (B, G, D), float32, of k, v (B, T, G, d)
    under the decays to the sequence's end w (B, T, G), at most one; ``c``
    (D,) is ``phi`` of a vector of ones, the constant ``phi(u)`` holds beside
    ``u_a u_{a+s}`` at ``s d + a``."""
    b, t, groups, d = k.shape
    if not tiles(t, d):
        raise ValueError(f"retention_end_state: {t} positions of heads {d} "
                         f"wide do not tile (heads of 128 lanes, whole "
                         f"blocks of {ROWS} positions)")
    f32 = jnp.float32
    S, z = _call(c.astype(f32).reshape(-1, d), k.reshape(b, t, groups * d),
                 v.reshape(b, t, groups * d), w.astype(f32))
    return S, z.reshape(b, groups, -1)
