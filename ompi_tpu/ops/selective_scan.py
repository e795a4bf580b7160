"""The selective (Mamba-1) scan over whole sequences as one pallas pass: a
decoder's prefill on a TPU (``models/selective.fused`` is the rule).

    S_t = exp(dt_t A) * S_{t-1} + (dt_t x_t) (x) B_t;      y_t = S_t C_t

``A`` is ``(N, Di)``, a decay a channel *and* a state element, so a chunk of
positions has no matrix form (Mamba-2's has: one decay a head,
``ops/ssm_scan.py``) and the positions go one at a time.  As ``jax.numpy``
that is a ``lax.scan`` whose every step crosses the HBM with the state, or a
``(T, Di, N)`` float32 temporary: 5.3 GB a sequence at 16k positions of 5120
channels.  Here the state never leaves the chip.

Layout.  A grid cell is one sequence, one block of 1024 channels and one chunk
of :data:`CHUNK` positions, the chunks innermost and in order.  The block's
state is ``(N, 8, 128)``: a state element a vreg, the channels on its 8
sublanes and 128 lanes, 16 vregs at ``N`` 16, which the loop over a chunk's
positions carries in registers; the VMEM scratch holds it only from one chunk
to the next.  A position's ``dt`` and ``x`` are one ``(8, 128)`` tile each
(the operands are ``(B, T, Di / 128, 128)``, which is the rows of ``(B, T,
Di)`` as they lie), so ``exp(dt A_n)``, the update and ``S_n C_n`` are plain
elementwise operations on whole vregs with no broadcast along an axis of the
tile and no sum over lanes or sublanes: ``B_t`` and ``C_t`` are ``N`` scalars
a position, read from SMEM (the operands flat, a chunk's a row) and splat.  The
work is the vector unit's: about six operations and one exponential a
(channel, state element) and position.

Returns ``y`` float32 and the state after the last position ``(B, N, Di)``
float32.  No backward pass (a trainer keeps ``selective.scan``).  Like the
other kernels here it always compiles for the TPU; :func:`tiles` says where a
caller takes it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["selective_scan", "tiles", "CHUNK", "CHANNELS"]

LANES, SUBLANES = 128, 8
CHANNELS = LANES * SUBLANES     # a block's channels: one vreg a state element
CHUNK = 128                     # positions a grid cell
_UNROLL = 4


def tiles(t: int, d_inner: int, d_state: int) -> bool:
    """True where the kernel takes sequences of ``t`` positions of
    ``d_inner`` channels over ``d_state`` state elements: whole chunks and
    whole blocks (nothing is padded here), and a block's state in registers
    beside a position's operands."""
    return (t > 0 and t % CHUNK == 0 and d_inner % CHANNELS == 0
            and 0 < d_state <= 32)


def _kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, y_ref, end_ref, s_ref, *,
            n: int):
    """One (sequence, block of channels, chunk) cell: the chunk's positions
    in order against the block's state ``s_ref`` (N, 8, 128), which the
    sequence's first chunk clears and its last hands out as ``end_ref``."""
    from jax import lax

    from ompi_tpu.ops._pallas import pl

    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    a = a_ref[...]                                      # (N, 8, 128)

    def position(t, S):
        dt, x = dt_ref[0, t], x_ref[0, t]               # (8, 128)
        dtx = dt * x
        y, rows = jnp.zeros_like(dt), []
        for i in range(n):
            row = jnp.exp(dt * a[i]) * S[i] + dtx * b_ref[0, 0, t * n + i]
            rows.append(row)
            y = y + row * c_ref[0, 0, t * n + i]
        y_ref[0, t] = y
        return rows

    def positions(u, S):        # unrolled by hand: Mosaic unrolls all or none
        for k in range(_UNROLL):
            S = position(u * _UNROLL + k, S)
        return S

    state = lax.fori_loop(0, CHUNK // _UNROLL, positions,
                          [s_ref[i] for i in range(n)])
    for i in range(n):
        s_ref[i] = state[i]

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _():
        end_ref[0] = s_ref[...]


@jax.jit
def selective_scan(x, dt, a, b, c):
    """x, dt: (B, T, Di); a: (N, Di); b, c: (B, T, N); from a zero state.
    Returns ``y`` (B, T, Di) float32 with ``y_t = S_t c_t`` and the last
    state (B, N, Di) float32."""
    from ompi_tpu.ops._pallas import pallas_call, pl, pltpu

    B, T, Di = x.shape
    N = a.shape[0]
    if not tiles(T, Di, N):
        raise ValueError(f"selective_scan: {T} positions of {Di} channels "
                         f"over {N} state elements do not tile")
    f32 = jnp.float32
    J = Di // LANES
    x4, dt4 = (t.astype(f32).reshape(B, T, J, LANES) for t in (x, dt))
    a3 = a.astype(f32).reshape(N, J, LANES)
    chunks = T // CHUNK
    flat = [t.astype(f32).reshape(B * chunks, 1, CHUNK * N) for t in (b, c)]
    scalars = pl.BlockSpec((1, 1, CHUNK * N),
                           lambda s, j, i: (s * chunks + i, 0, 0),
                           memory_space=pltpu.SMEM)
    rows = pl.BlockSpec((1, CHUNK, SUBLANES, LANES),
                        lambda s, j, i: (s, i, j, 0))
    y, end = pallas_call(
        functools.partial(_kernel, n=N),
        grid=(B, J // SUBLANES, chunks),
        in_specs=[scalars, scalars, rows, rows,
                  pl.BlockSpec((N, SUBLANES, LANES),
                               lambda s, j, i: (0, j, 0))],
        out_specs=[rows, pl.BlockSpec((1, N, SUBLANES, LANES),
                                      lambda s, j, i: (s, 0, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, T, J, LANES), f32),
                   jax.ShapeDtypeStruct((B, N, J, LANES), f32)],
        scratch_shapes=[pltpu.VMEM((N, SUBLANES, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="selective_scan",
    )(*flat, x4, dt4, a3)
    return y.reshape(B, T, Di), end.reshape(B, N, Di)
