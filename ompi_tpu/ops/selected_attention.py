"""One query a sequence against the positions a mask selects of a stacked
K/V cache, as one pallas pass: the cached step's sibling of
``ops/masked_attention.py``, for ``models/sparse_index.py``'s decoder where
the selection is a large part of the cache.

XLA reads a selection by a gather, which on this chip costs a row's 14.5 ns
whether or not the next row is its neighbour, and attention then reads the
gathered rows twice more.  Where a query selects a quarter of its cache that
is slower than reading all of it once at the HBM's rate, which is what this
does: a flash-decode pass, a grid cell a (sequence, block of positions), the
block's rows streamed through VMEM, scores and weights only at the
positions the mask allows, the running (max, normaliser, context) of every
query head in scratch across a sequence's blocks.

The operand is the whole stack ``(L, B, Tmax, 2 Hkv D)`` and the layer a
prefetched scalar that the block's index map reads, as ``grouped_matmul``'s
group is: a layer sliced out first would be a copy of a layer.  A position's
row holds its K heads and then its V heads, each 128 lanes or a multiple: a
block is rows of positions, and a head's K or V a lane-aligned slice of it,
so K and V are one operand and nothing cuts them apart.  K/V head g serves
the query heads ``g r .. g r + r - 1`` and is read once for them.

No backward pass (a decoder's step has none).  No block is skipped: a step
whose cache is far longer than its selection should gather
(``sparse_index.streams`` says from where).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["selected_attention", "tiles"]

_NEG = -1e30
# Positions a block.  One layer of cell 6 on the chip (64 sequences, 8192
# positions, rows of 2 KB: 1.07 GB; PERF.md section 6, PR 43): 2.43 ms at
# 256, 1.58 at 512, 1.44 at 1024 (745 GB/s), 1.44 at 2048: a grid step's
# own third of a microsecond beside a block's DMA, and nothing past 2 MB.
_BLOCK = 1024
_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b


def tiles(t_max: int, head_dim: int) -> bool:
    """True where the kernel takes a cache of ``t_max`` positions of heads
    ``head_dim`` wide: a head is a block of 128 lanes, the cache whole
    blocks of positions."""
    return head_dim % 128 == 0 and t_max % _BLOCK == 0


def _kernel(layer, q_ref, kv_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float):
    from jax import lax

    from ompi_tpu.ops._pallas import pl

    del layer               # read by the index map of ``kv_ref``
    kv_heads, _, head_dim = q_ref.shape
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    allowed = mask_ref[...] != 0                            # (1, block)
    for g in range(kv_heads):
        k = kv_ref[:, g * head_dim:(g + 1) * head_dim]      # (block, D)
        v = kv_ref[:, (kv_heads + g) * head_dim:
                   (kv_heads + g + 1) * head_dim]
        s = lax.dot_general(q_ref[g], k, _NT,
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(allowed, s, _NEG)                     # (r, block)
        m = m_ref[g]                                        # (r, 1)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        # a head that has seen no allowed position yet keeps l = 0:
        # exp(s - m_new) would be 1 at every masked position there
        p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l_ref[g] = l_ref[g] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[g] = acc_ref[g] * corr + lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_ref[g] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@jax.jit
def _call(layer, q4, kv, mask3):
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    b, kv_heads, group, d = q4.shape
    t_max, width = kv.shape[2], kv.shape[3]
    return pallas_call(
        functools.partial(_kernel, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t_max // _BLOCK),
            in_specs=[
                pl.BlockSpec((None, kv_heads, group, d),
                             lambda b, j, layer: (b, 0, 0, 0)),
                pl.BlockSpec((None, None, _BLOCK, width),
                             lambda b, j, layer: (layer[0], b, j, 0)),
                pl.BlockSpec((None, 1, _BLOCK),
                             lambda b, j, layer: (b, 0, j)),
            ],
            out_specs=pl.BlockSpec((None, kv_heads, group, d),
                                   lambda b, j, layer: (b, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((kv_heads, group, 1), jnp.float32),
                            pltpu.VMEM((kv_heads, group, 1), jnp.float32),
                            pltpu.VMEM((kv_heads, group, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q4.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="selected_attention",
    )(layer, q4, kv, mask3)


def selected_attention(q, kv, mask, layer):
    """Softmax attention of one query a sequence, q (B, 1, H, D), over the
    positions ``mask`` (B, Tmax) bool or int8 allows of layer ``layer`` (a
    traced int32) of kv (L, B, Tmax, 2 Hkv D), a position's K heads and then
    its V heads in one row; scaled by ``D ** -0.5``; K/V head g serves the
    query heads ``g r .. g r + r - 1`` (``r = H / Hkv``).  Products in kv's
    type, sums float32; (B, 1, H, D) float32, zeros for a sequence whose
    mask allows nothing."""
    b, _, heads, d = q.shape
    t_max, width = kv.shape[2], kv.shape[3]
    kv_heads = width // (2 * d)
    if not tiles(t_max, d) or width != 2 * kv_heads * d or heads % kv_heads:
        raise ValueError(
            f"selected_attention: {t_max} positions of rows {width} wide "
            f"under {heads} heads of {d} do not tile (blocks of {_BLOCK} "
            f"positions, 128 lanes a head, K heads then V heads a row)")
    out = _call(jnp.asarray(layer, jnp.int32).reshape(1),
                q.astype(kv.dtype).reshape(b, kv_heads, heads // kv_heads, d),
                kv, mask.astype(jnp.int8).reshape(b, 1, t_max))
    return out.reshape(b, 1, heads, d)
