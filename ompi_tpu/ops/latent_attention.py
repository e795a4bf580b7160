"""Causal attention of whole sequences whose keys are wider than their
values, as one pallas kernel: the forward pass of ``models/mla.py``'s
prefill, where a head's key is its own part (``nope`` wide, multiplied out of
the latent) beside a part all heads share (``rope`` wide) and its value is
``v_dim`` wide.

The jnp form materialises every head's (T, T) float32 scores in HBM (at T =
16,384 and 16 heads 17 GB a sequence); this keeps a (block, block) tile of
one head's scores in VMEM, streams the head's keys and values and the shared
key part past it and holds the running (max, normaliser, accumulator) of the
tile's queries, as ``ops/flash_attention.py``'s forward does for heads of one
width.  A tile's scores are two products, ``q_n . k_n`` and ``q_r . k_r``, so
the shared part is read as it lies, ``(B, T, rope)``, and never copied out to
the heads; a head's own keys and its values are the two lane blocks of its
``nope + v_dim`` columns of the latent's up-projection ``(B, T, H (nope +
v_dim))``, read in place.  A grid cell is (batch, head, q block); the keys
from position 0 to the cell's last query are visited, the blocks the diagonal
crosses under the mask, and none above it.

Queries and keys start at position 0 and are as many (a prefill).  No
backward pass: a trainer takes the jnp form.  The keys of one head enter a
cell as one VMEM block, so a sequence is at most ``MAX_ROWS`` positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["latent_attention", "tiles", "MAX_ROWS"]

_NEG = -1e30
_BLOCK = 512            # rows of q, and of k, a tile (as flash_attention's)
# three whole-sequence operands a cell (k_n, v, and k_r padded to 128 lanes),
# double buffered: 48 MiB of bfloat16 at this many rows
MAX_ROWS = 32_768
_VMEM_LIMIT_BYTES = 96 << 20
_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b


def tiles(t: int, nope: int, v_dim: int) -> bool:
    """True where the kernel takes sequences of ``t`` positions of heads
    whose own key part is ``nope`` and whose values are ``v_dim`` wide: each
    a block of 128 lanes (the positions are padded to whole tiles here)."""
    return nope == v_dim == 128 and 0 < t <= MAX_ROWS


def _kernel(q_ref, kn_ref, kr_ref, v_ref, o_ref, *, scale: float, nope: int,
            block: int):
    """One (batch, head, q block) cell: the K/V blocks up to the diagonal
    streamed past the tile, the softmax online in float32."""
    from jax import lax

    from ompi_tpu.ops._pallas import pl

    iq = pl.program_id(2)
    q_n, q_r = q_ref[0, 0, :, :nope], q_ref[0, 0, :, nope:]     # (bq, N | P)

    def step(j, carry, masked):
        m, l, acc = carry
        ks = pl.ds(pl.multiple_of(j * block, block), block)
        v_blk = v_ref[0, ks, :]
        s = (lax.dot_general(q_n, kn_ref[0, ks, :], _NT,
                             preferred_element_type=jnp.float32)
             + lax.dot_general(q_r, kr_ref[0, ks, :], _NT,
                               preferred_element_type=jnp.float32)) * scale
        if masked:      # the one block the diagonal crosses: j == iq
            rows = lax.broadcasted_iota(jnp.int32, (block, block), 0)
            cols = lax.broadcasted_iota(jnp.int32, (block, block), 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        return (m_new, l * corr + p.sum(axis=-1),
                acc * corr[:, None] + lax.dot_general(
                    p.astype(v_blk.dtype), v_blk, _NN,
                    preferred_element_type=jnp.float32))

    carry = (jnp.full((block,), _NEG, jnp.float32),
             jnp.zeros((block,), jnp.float32),
             jnp.zeros((block, v_ref.shape[-1]), jnp.float32))
    carry = lax.fori_loop(0, iq, functools.partial(step, masked=False), carry)
    _m, l, acc = step(iq, carry, masked=True)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _call(q4, kv3, kr3, scale: float, sizes: tuple):
    """q4 (B, H, T, N + P), kv3 (B, T, H (N + W)), kr3 (B, T, P) -> (B, T,
    H W); T whole tiles."""
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    nope, rope, v_dim = sizes
    b, heads, t, _ = q4.shape
    block = min(_BLOCK, t)
    return pallas_call(
        functools.partial(_kernel, scale=scale, nope=nope, block=block),
        grid=(b, heads, t // block),
        in_specs=[
            pl.BlockSpec((1, 1, block, nope + rope),
                         lambda b, h, i: (b, h, i, 0)),
            # a head's columns of the up-projection: its keys, then its values
            pl.BlockSpec((1, t, nope), lambda b, h, i: (b, 0, 2 * h)),
            pl.BlockSpec((1, t, rope), lambda b, h, i: (b, 0, 0)),
            pl.BlockSpec((1, t, v_dim), lambda b, h, i: (b, 0, 2 * h + 1)),
        ],
        out_specs=pl.BlockSpec((1, block, v_dim), lambda b, h, i: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct((b, t, heads * v_dim), q4.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="latent_attention",
    )(q4, kv3, kr3, kv3)


def latent_attention(q, kv, k_r, scale: float):
    """Causal softmax attention of q (B, T, H, nope + rope) over the keys
    ``[kv[..., :nope], k_r]`` and the values ``kv[..., nope:]``, kv (B, T, H,
    nope + v_dim) and k_r (B, T, rope) shared by the heads; position t sees
    positions 0 .. t.  Scores times ``scale``.  Products in q's type, sums
    float32; (B, T, H, v_dim) in q's type.  The positions are padded to whole
    tiles with keys no query sees and queries that are dropped."""
    b, t, heads, width = q.shape
    rope = k_r.shape[-1]
    nope, v_dim = width - rope, kv.shape[-1] - (width - rope)
    if not tiles(t, nope, v_dim):
        raise ValueError(f"latent_attention: {t} positions of heads {nope} + "
                         f"{rope} and {v_dim} wide do not tile (128 lanes a "
                         f"part, at most {MAX_ROWS} positions)")
    pad = -t % min(_BLOCK, -(-t // 128) * 128)
    if pad:
        q, kv = (jnp.pad(y, ((0, 0), (0, pad), (0, 0), (0, 0)))
                 for y in (q, kv))
        k_r = jnp.pad(k_r, ((0, 0), (0, pad), (0, 0)))
    out = _call(q.swapaxes(1, 2), kv.reshape(b, t + pad, -1), k_r,
                float(scale), (nope, rope, v_dim))
    return out[:, :t].reshape(b, t, heads, v_dim)
