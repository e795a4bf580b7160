"""The delta rule's cached step as one pallas pass over a layer's matrix
states: ``models/kda.update`` on a TPU.

A layer carries a ``(K, K)`` float32 matrix, key by value, for every
(sequence, head): 805 MB at Kimi-Linear's 384 x 32 x 128 x 128.  One new
position decays it, reads two products out of it and adds one outer product:

    S' = diag(exp g) S;  a = S'^T k;  c = S'^T q;  u = beta (v - a)
    o  = c + (q . k) u;  S_new = S' + k u^T

No ``jax.numpy`` form of this schedules as one fusion on the v5e's compiler:
the reductions over the key axis and the elementwise write of the same
operand become two fused computations that each take the state as a
parameter, three passes over it where a read and a write are needed
(``ROADMAP.md`` S13.2).  Here a block of one sequence's heads is copied into
VMEM, swept twice there (the two reductions, then the write once ``u`` is
known) and copied back *into the buffer it came from*
(``input_output_aliases``): the state crosses the HBM once each way, and no
second copy of a layer's state exists.

Layout.  In a ``(K, K)`` tile "key by value" the key axis lies on sublanes
and the value axis on lanes.  ``exp g``, ``k`` and ``q`` multiply along the
keys, so the kernel needs them as columns; they arrive as rows, ``(heads,
K)`` with K on lanes, and are transposed in VMEM, a block's three at once
(handing them over as ``(B, K, heads)`` would pad 32 heads to 128 lanes in
the HBM, four times their bytes).  ``v``, ``u`` and ``o`` run along the
values and stay rows.  The sums over keys are elementwise adds across a
matrix's sublane tiles and one 8-to-1 sublane reduce, all on the vector
unit in float32: the MXU would round a float32 product to bfloat16 passes
and load every (sequence, head) matrix as weights for two rows.

Everything is float32, as the configuration's ``kda_state_dtype`` says.
No backward pass (a decoder's step has none).  Like the other kernels here
it always compiles for the TPU; :func:`block` says where a caller takes it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ompi_tpu.ops._chip import _VMEM_BUDGET_BYTES

__all__ = ["kda_update", "block"]


def _working_set_bytes(heads: int, K: int) -> int:
    """VMEM the kernel holds at a block of ``heads`` matrices: the state in
    and the state out twice each (the pipeline copies the next block while
    this one is swept), and likewise the four vectors in, ``beta`` padded to
    a lane row a head, and ``o`` out."""
    return 2 * 4 * (2 * heads * K * K + 5 * heads * K + heads * 128)


def block(tpu: bool, dtype, heads: int, K: int):
    """The block ``(1, heads a block, K, K)`` of a ``(B, heads, K, K)`` state
    of ``dtype`` that :func:`kda_update` streams, or None where the
    ``jax.numpy`` form runs: off a mesh of TPUs (``tpu``: attached, or
    described for a compile; the kernel compiles for nothing else), for a
    state that is not float32, for a head that is not whole tiles of 128
    lanes, and where not even eight heads' matrices fit the kernel's VMEM
    budget twice over each way.  The most heads that fit, a divisor of
    ``heads`` and whole sublane tiles of eight (the vectors' blocks), all of
    a sequence's where they do.  All static: a program's steps take the
    kernel in every layer or in none."""
    if not tpu or jnp.dtype(dtype) != jnp.float32 or K % 128:
        return None
    hb = next((hb for hb in range(heads - heads % 8, 0, -8)
               if heads % hb == 0
               and _working_set_bytes(hb, K) <= _VMEM_BUDGET_BYTES), None)
    return hb and (1, hb, K, K)


def _kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, o_ref, s_out):
    heads, K = q_ref.shape
    q, k = q_ref[...], k_ref[...]                           # (heads, K)
    decay = jnp.exp(g_ref[...])
    qk = jnp.sum(q * k, axis=-1, keepdims=True)             # (heads, 1)
    beta = beta_ref[...]                                    # (heads, 1)
    # the three that multiply along the keys, as columns: (K, heads) each
    decay_c, k_c, q_c = decay.T, k.T, q.T
    for h in range(heads):
        at = slice(h, h + 1)
        S = s_ref[h] * decay_c[:, at]                       # (K, K)
        a = jnp.sum(S * k_c[:, at], axis=0, keepdims=True)  # (1, K)
        c = jnp.sum(S * q_c[:, at], axis=0, keepdims=True)
        u = beta[at] * (v_ref[at, :] - a)
        o_ref[at, :] = c + qk[at] * u
        s_out[h] = S + k_c[:, at] * u


@jax.jit
def _call(state, q, k, v, g, beta):
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    B, H, K, _ = state.shape
    hb = block(True, state.dtype, H, K)[1]
    # (B, H, ...) -> (B, H / hb, hb, ...): a block's last two axes are then
    # whole axes of its operand; free, hb being whole sublane tiles
    rows = [y.reshape(B, H // hb, hb, K) for y in (q, k, v, g)]
    vector = pl.BlockSpec((None, None, hb, K), lambda b, j: (b, j, 0, 0))
    matrix = pl.BlockSpec((None, hb, K, K), lambda b, j: (b, j, 0, 0))
    o, state = pallas_call(
        _kernel,
        grid=(B, H // hb),
        in_specs=[vector, vector, vector, vector,
                  pl.BlockSpec((None, None, hb, 1),
                               lambda b, j: (b, j, 0, 0)),
                  matrix],
        out_specs=(vector, matrix),
        out_shape=(jax.ShapeDtypeStruct((B, H // hb, hb, K), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="kda_update",
    )(*rows, beta.reshape(B, H // hb, hb, 1), state)
    return o.reshape(B, H, K), state


def kda_update(state, q, k, v, g, beta):
    """One new position against a layer's carried states: ``state`` (B, H,
    K, K) float32, key by value; q, k, v, g (B, H, K) and beta (B, H)
    float32, g the log decay.  Returns o (B, H, K) and the new state, which
    is written into ``state``'s buffer where the caller donates it."""
    _, H, K, _ = state.shape
    if block(True, state.dtype, H, K) is None:
        raise ValueError(
            f"kda_update: a {state.dtype} state {state.shape} does not tile "
            f"(float32, heads 128 lanes or a multiple wide, and eight of "
            f"them within {_VMEM_BUDGET_BYTES >> 20} MiB of VMEM)")
    return _call(state, *(y.astype(jnp.float32) for y in (q, k, v, g, beta)))
