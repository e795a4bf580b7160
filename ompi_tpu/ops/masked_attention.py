"""Attention under an arbitrary mask, grouped K/V heads, as one pallas kernel:
the forward pass of ``models/sparse_index.py``'s prefill, where each query
attends to the positions an index selected for it.

The jnp form materialises a slice's scores for every head in HBM, three
times over (scores, weights, their sum); this keeps a (block_q, block_k)
tile of one K/V head's scores in VMEM, streams K/V and mask blocks past it
and holds the running (max, normaliser, accumulator) of the query heads that
K/V head serves, so a slice moves q, k, v, the mask and the output through
HBM once a K/V head.  A grid cell is (batch, K/V head, q block, k block),
the last an ``arbitrary`` axis that the scratch accumulators live across;
the ``r`` query heads of a K/V head are the columns ``[g r D, (g + 1) r D)``
of q's rows and share the cell's K, V and mask tiles.

No backward pass: a trainer takes the jnp form.  A mask that leaves whole
tiles empty (a trained index's) is still read tile by tile: skipping those
would want the tiles' flags prefetched, which is ROADMAP's block-sparse
prefill kernel.  What is skipped is the end of the keys: a caller whose
queries see no key from some length on hands that length over as ``k_len``,
a traced scalar that is prefetched (``PrefetchScalarGridSpec``): a grid step
past it maps to the last key block within it, which is fetched once, and
does nothing, so one traced shape serves every length (``models/
block_select.py``'s scan over a prompt's slices).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["masked_attention", "tiles"]

_NEG = -1e30
_BLOCK = 512            # rows of q, and of k, a tile (as flash_attention's)
_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b


def tiles(t_q: int, head_dim: int) -> bool:
    """True where the kernel takes ``t_q`` query rows of heads ``head_dim``
    wide: a head is a block of 128 lanes and the mask's int8 tile is 32 rows
    (the keys are padded to whole blocks here)."""
    return head_dim % 128 == 0 and t_q % 32 == 0


def _kernel(*refs, scale: float, group: int, head_dim: int):
    """One (block_q, block_k) tile.  ``refs`` begin with the prefetched key
    length where the call has one."""
    from jax import lax

    from ompi_tpu.ops._pallas import pl

    *k_len, q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref = refs
    j = pl.program_id(3)
    first = j * k_ref.shape[1]          # the tile's first key
    k_len = k_len[0][0] if k_len else None

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile():
        k_blk, v_blk = k_ref[0], v_ref[0]                       # (bk, D)
        allowed = mask_ref[0] != 0                              # (bq, bk)
        if k_len is not None:
            allowed &= first + lax.broadcasted_iota(
                jnp.int32, (1, allowed.shape[1]), 1) < k_len
        for h in range(group):
            q = q_ref[0, :, h * head_dim:(h + 1) * head_dim]    # (bq, D)
            s = lax.dot_general(q, k_blk, _NT,
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(allowed, s, _NEG)
            m = m_ref[h]                                        # (bq, 1)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            # a row that has seen no allowed key yet keeps l = 0: exp(s -
            # m_new) would be 1 at every masked key there
            p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + lax.dot_general(
                p.astype(v_blk.dtype), v_blk, _NN,
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    if k_len is None:
        tile()
    else:
        pl.when(first < k_len)(tile)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        for h in range(group):
            o_ref[0, :, h * head_dim:(h + 1) * head_dim] = (
                acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(4,))
def _call(q3, k3, v3, mask, kv_heads: int, k_len=None):
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    b, t_q, width = q3.shape
    t_k = k3.shape[1]
    d = k3.shape[2] // kv_heads
    group = width // (kv_heads * d)
    block_q = _BLOCK if t_q % _BLOCK == 0 else t_q
    block_k = min(_BLOCK, t_k)

    def key_block(j, n):
        """The key block grid step j reads: its own, or the last one within
        the prefetched length ``n`` from there on."""
        return jnp.minimum(j, (n[0][0] - 1) // block_k) if n else j

    grid = dict(
        grid=(b, kv_heads, t_q // block_q, t_k // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, group * d),
                         lambda b, g, i, j, *n: (b, i, g)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, g, i, j, *n: (b, key_block(j, n), g)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, g, i, j, *n: (b, key_block(j, n), g)),
            pl.BlockSpec((1, block_q, block_k),
                         lambda b, g, i, j, *n: (b, i, key_block(j, n))),
        ],
        out_specs=pl.BlockSpec((1, block_q, group * d),
                               lambda b, g, i, j, *n: (b, i, g)),
        scratch_shapes=[pltpu.VMEM((group, block_q, 1), jnp.float32),
                        pltpu.VMEM((group, block_q, 1), jnp.float32),
                        pltpu.VMEM((group, block_q, d), jnp.float32)])
    if k_len is None:
        lengths = ()
    else:
        lengths = (jnp.asarray(k_len, jnp.int32).reshape(1),)
        grid = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **grid))
    return pallas_call(
        functools.partial(_kernel, scale=d ** -0.5, group=group, head_dim=d),
        **grid,
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="masked_attention",
    )(*lengths, q3, k3, v3, mask)


def masked_attention(q, k, v, mask, k_len=None):
    """Softmax attention of q (B, Tq, H, D) over k, v (B, Tk, Hkv, D) at the
    pairs ``mask`` (B, Tq, Tk) bool allows, scaled by ``D ** -0.5``; K/V
    head g serves the query heads ``g r .. g r + r - 1`` (``r = H / Hkv``).
    Products in q's type, sums float32; (B, Tq, H, D) in q's type.  Every
    query needs a key it may see.  The keys are padded to whole blocks with
    keys no query may see.

    ``k_len`` (an int32 scalar, traced or not, 1 to Tk): no query sees a key
    at or past it, whatever the mask says there, and the key blocks past it
    are neither fetched nor read; the result is that of the call on
    ``k[:, :k_len]``, ``v[:, :k_len]``, ``mask[..., :k_len]``."""
    b, t_q, heads, d = q.shape
    t_k, kv_heads = k.shape[1], k.shape[2]
    if not tiles(t_q, d):
        raise ValueError(f"masked_attention: {t_q} queries of heads {d} wide "
                         f"do not tile (32 rows, 128 lanes)")
    block_k = min(_BLOCK, -(-t_k // 128) * 128)
    pad = -t_k % block_k
    mask = mask.astype(jnp.int8)
    if pad:
        k, v = (jnp.pad(y, ((0, 0), (0, pad), (0, 0), (0, 0)))
                for y in (k, v))
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, pad)))
    out = _call(q.reshape(b, t_q, heads * d),
                k.reshape(b, t_k + pad, kv_heads * d),
                v.reshape(b, t_k + pad, kv_heads * d), mask, kv_heads, k_len)
    return out.reshape(b, t_q, heads, d)
