"""One absorbed query a sequence against its latent cache, as one pallas
pass: the cached step of ``models/mla.py`` where the cache is long.

The ``jax.numpy`` form reads a layer's cache twice, once for the scores
(``q_abs . row`` over all ``kv_rank + rope`` lanes) and once for the context
(``sum_s p_s row_s[:kv_rank]``): two fusions, each with the cache as a
parameter, and no form of ``jax.numpy`` makes them one.  This is a
flash-decode pass, a grid cell a (sequence, block of positions): the block's
rows come into VMEM once and are used for both products, the running (max,
normaliser, context) of every head in float32 scratch across a sequence's
blocks, as ``ops/selected_attention.py`` keeps them.  All heads read the same
rows (that is what the latent is), so a block is one operand of both
products and nothing is cut out of it but the context's lane-aligned
``[:kv_rank]``.

The operand is a layer's own cache ``(B, Tmax, kv_rank + rope)`` as the
carry holds it.  The step's position is a prefetched scalar: positions past
it are masked, and a block wholly past it is neither copied (its index map
names the last live block again) nor computed.  A layer with an index hands
the positions it selected over as one more operand, ``chosen`` (B, Tmax): a
block's flags come in beside its rows and mask its scores, so the read of a
selection is the same one pass over the live rows (the rows are shared by all
heads, so a row not chosen is a row not needed by any head; skipping it would
be a gather, which costs this chip more a row than the stream does:
``models/sparse_index._STREAM_UP_TO``).

No backward pass (a decoder's step has none).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["latent_decode", "tiles"]

_NEG = -1e30
# Positions a block, and the shortest cache the kernel takes.  One layer's
# step alone on the chip, the cache a loop's carry in the layout cell 10's
# program gives it (rows of 576 stored as five lane tiles: 671 MB a layer for
# 32 sequences of 16,384; PERF.md section 6, PR 57, ``micro_a.json``): 1.436
# ms at 256, 1.008 at 512, 0.895 at 1024 (750 GB/s), 0.895 at 2048 and 4096,
# where ``jax.numpy`` takes 1.620.  The same bytes as shorter caches stay
# ahead down to one block a sequence (512 sequences of 1024: 0.944 for 1.698;
# 384 of 512 under 32 heads at a block of 512: 0.461 for 0.656), so whole
# blocks are the only length the rule asks for.
_BLOCK = 1024
_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b


def tiles(t_max: int, rank: int) -> bool:
    """True where the kernel takes a cache of ``t_max`` positions whose
    latent is ``rank`` wide: whole lane tiles (the context's slice of a row
    is then aligned; the rest of a row may have any width, the scores
    contract all of it) and whole blocks of positions."""
    return rank % 128 == 0 and t_max % _BLOCK == 0


def _kernel(pos, q_ref, c_ref, *refs, scale: float, rank: int):
    """One (sequence, block of positions) cell.  ``refs`` begin with the
    block's flags (1, block) where the call has a selection."""
    from jax import lax

    from ompi_tpu.ops._pallas import pl

    *chosen_ref, o_ref, m_ref, l_ref, acc_ref = refs
    block = c_ref.shape[0]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block <= pos[0])       # block 0 always: l > 0 at the end
    def _():
        rows = c_ref[...]                                   # (block, R + P)
        s = lax.dot_general(q_ref[...], rows, _NT,
                            preferred_element_type=jnp.float32) * scale
        at = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
        allowed = at <= pos[0]
        if chosen_ref:
            allowed &= chosen_ref[0][...] != 0
        s = jnp.where(allowed, s, _NEG)                     # (H, block)
        m = m_ref[...]                                      # (H, 1)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)      # a live block: m_new is a real score
        if chosen_ref:      # a block none of whose rows is chosen: m_new is
            p = jnp.where(allowed, p, 0.0)      # _NEG, exp(s - m_new) one
        corr = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
            p.astype(rows.dtype), rows[:, :rank], _NN,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...] / (jnp.maximum(l_ref[...], 1e-30)
                                     if chosen_ref else l_ref[...])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _call(pos, q, cache, scale: float, rank: int, chosen=None):
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    b, heads, width = q.shape
    t_max = cache.shape[1]

    def live_block(j, pos):
        """Block ``j``, or past the last live block that block again, so
        that nothing new is copied."""
        return jnp.minimum(j, pos[0] // _BLOCK)

    flags = () if chosen is None else (
        pl.BlockSpec((None, 1, _BLOCK),
                     lambda b, j, pos: (b, 0, live_block(j, pos))),)
    return pallas_call(
        functools.partial(_kernel, scale=scale, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, t_max // _BLOCK),
            in_specs=[
                pl.BlockSpec((None, heads, width),
                             lambda b, j, pos: (b, 0, 0)),
                pl.BlockSpec((None, _BLOCK, width),
                             lambda b, j, pos: (b, live_block(j, pos), 0)),
                *flags,
            ],
            out_specs=pl.BlockSpec((None, heads, rank),
                                   lambda b, j, pos: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, rank), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="latent_decode",
    )(pos, q, cache, *(() if chosen is None else (chosen,)))


def latent_decode(q_abs, cache, pos, scale: float, rank: int, chosen=None):
    """The absorbed step's context: softmax over positions ``0 .. pos`` (a
    traced int32) of ``q_abs . cache_s`` times ``scale``, q_abs (B, H, rank +
    rope) against cache (B, Tmax, rank + rope), then ``sum_s p_s cache_s[:
    rank]``.  ``chosen`` (B, Tmax) bool or int8: of those positions the ones
    it allows alone (a selection; zeros for a sequence it allows nothing).
    Products in the cache's type (the weights cast to it), sums float32; (B,
    H, rank) float32."""
    t_max, width = cache.shape[1:]
    if not tiles(t_max, rank) or q_abs.shape[-1] != width:
        raise ValueError(
            f"latent_decode: {t_max} positions of rows {width} wide, the "
            f"latent {rank} of them, under queries {q_abs.shape[-1]} wide do "
            f"not tile (blocks of {_BLOCK} positions, a latent of whole 128 "
            f"lanes)")
    if chosen is not None:
        chosen = chosen.astype(jnp.int8).reshape(cache.shape[0], 1, t_max)
    return _call(jnp.asarray(pos, jnp.int32).reshape(1),
                 q_abs.astype(cache.dtype), cache, float(scale), rank, chosen)
