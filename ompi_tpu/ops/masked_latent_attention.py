"""Attention of a slice of queries under an arbitrary mask where a head's
keys are wider than its values, as one pallas kernel: the forward pass of
``models/mla.py``'s prefill in a layer with an index, where each query
attends to the positions the index selected for it.

``ops/latent_attention.py`` is causal and holds a head's keys of the whole
sequence in VMEM; ``ops/masked_attention.py`` takes a mask and heads of one
width.  This is the second's grid with the first's operands: a head's key is
its own part (``nope`` wide, a lane block of the latent's up-projection ``(B,
T, H (nope + v_dim))``, read in place) beside the part all heads share
(``rope`` wide, read as ``128 / rope`` copies side by side, against a head's
``rope`` lanes of ``(B, Tq, H rope)`` whose neighbours' lanes are zeroed), so
a tile's scores are two products and no key is ever copied out to the heads;
its value is the next lane block of the same columns.  A grid cell is
(batch, group of heads, k block): the ``_GROUP`` heads of a cell share the
mask's tile, which is read once for them (a mask read once a head is as many
bytes as the head's keys and values), and keep their running (max,
normaliser, accumulator) in float32 scratch across the ``arbitrary`` last
axis.  The queries are one block: a caller hands a slice of at most ``_ROWS``
at a time (``models/mla.py``: a ``lax.scan`` over a prompt's slices, one
traced shape).

``k_len`` is a prefetched scalar, as ``masked_attention``'s: no query sees a
key at or past it, a grid step past it names the last key block within it
again, which is not fetched twice, and does nothing.  A mask that leaves
whole tiles empty inside that length (a trained index's) is still read tile
by tile (``ROADMAP.md`` M18).  No backward pass: a trainer takes the jnp
form.

What a tile's body spends between its products, a float32 score of a head's
``(512, 512)`` tile: the add of the two products (a contraction 256 deep is
two passes of the 128-deep array on two units and the same add: the compiler
makes it, a scratch of ``[q_n | q_r]`` removes none), one select under the
mask, the row max, the subtraction of the running max, one multiply, ``exp2``,
the add into the row's sum and the cast to the compute type.  ``scale`` sits
in that one multiply, ``exp2((s - m) * scale * log2 e)``: the max is taken of
the unscaled scores (``scale`` is positive) and the multiply ``exp`` would
spend on ``log2 e`` applies it, where scaling ``q`` would round the queries
anew.  A row's running max starts at ``_FLOOR``, finite and far above
``_NEG``, so a score the mask set to ``_NEG`` underflows to an exact 0
whatever the row has seen and no second select is needed; a row of no key
ends with a sum of 0 and reads zeros.  The running max and sum are kept 128
lanes wide: a ``(512, 1)`` column is a lane broadcast through the cross-lane
unit at each use (three a head and tile, 768 of them a step, and the MXU
waited for them: 9658 cycles a step against 6565, PERF.md section 6, PR 78),
a max that fills its lanes is subtracted as it lies; and the sum is kept a
lane's part of it, added across the tile's four lane blocks on the vector
unit, and summed across lanes once, at the last tile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["masked_latent_attention", "jnp_form", "tiles"]

_NEG = -1e30
# what a row's running max starts from: far under any score and far enough
# above _NEG that a score set to _NEG is exp2 of about -2e29 times the scale
_FLOOR = -1e20
_LOG2_E = math.log2(math.e)
_ROWS = 512             # the most queries a call takes, one block
_BLOCK = 512            # keys a tile
_GROUP = 4              # heads a grid cell, which share a tile of the mask
_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b


def tiles(t_q: int, heads: int, nope: int, rope: int, v_dim: int) -> bool:
    """True where the kernel takes ``t_q`` queries of ``heads`` heads whose
    own key part is ``nope``, whose shared part is ``rope`` and whose values
    are ``v_dim`` wide: the own part and the values a block of 128 lanes
    each, a group's shared parts whole blocks of 128 lanes side by side, the
    mask's int8 tile 32 rows (the keys are padded to whole blocks here)."""
    return (nope == v_dim == 128 and 0 < rope <= 128 and 128 % rope == 0
            and heads % _GROUP == 0 and (_GROUP * rope) % 128 == 0
            and 0 < t_q <= _ROWS and t_q % 32 == 0)


def jnp_form(q, kv, k_r, mask, scale: float):
    """``masked_latent_attention`` in ``jax.numpy``, the slice's scores held
    for every head: what runs off TPUs and where the kernel does not tile.
    The result in float32."""
    f32 = jnp.float32
    nope = q.shape[-1] - k_r.shape[-1]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope],
                    preferred_element_type=f32)
         + jnp.einsum("bqhd,bkd->bhqk", q[..., nope:], k_r,
                      preferred_element_type=f32)) * scale
    allowed = mask[:, None]
    s = jnp.where(allowed, s, _NEG)
    w = jnp.where(allowed, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
    total = jnp.maximum(w.sum(axis=-1), 1e-30)              # (B, H, Tq)
    o = jnp.einsum("bhqk,bkhd->bqhd", w.astype(q.dtype), kv[..., nope:],
                   preferred_element_type=f32)
    return o / total.swapaxes(1, 2)[..., None]


def _kernel(k_len, qn_ref, qr_ref, kv_ref, kr_ref, mask_ref, o_ref, m_ref,
            l_ref, acc_ref, *, scale: float, nope: int, rope: int,
            v_dim: int):
    """One (block of keys) step of a (batch, group of heads) cell."""
    from jax import lax

    from ompi_tpu.ops._pallas import pl

    j = pl.program_id(2)
    block = kv_ref.shape[1]
    first = j * block                   # the tile's first key
    group, side = m_ref.shape[0], 128 // rope
    to_exp2 = scale * _LOG2_E

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(first < k_len[0])
    def _():
        k_r = kr_ref[0]                                     # (bk, 128)
        allowed = (mask_ref[0] != 0) & (
            first + lax.broadcasted_iota(jnp.int32, (1, block), 1)
            < k_len[0])                                     # (bq, bk)
        for h in range(group):
            cols = h * (nope + v_dim)
            k_n = kv_ref[0, :, cols:cols + nope]            # (bk, N)
            v = kv_ref[0, :, cols + nope:cols + nope + v_dim]
            q_n = qn_ref[0, :, h * nope:(h + 1) * nope]     # (bq, N)
            # the 128 lanes this head's shared part lies in; its
            # neighbours' lanes are zeros against k_r's copies
            q_r = qr_ref[0, :, h // side * 128:(h // side + 1) * 128]
            if side > 1:
                lane = lax.broadcasted_iota(jnp.int32, q_r.shape, 1)
                q_r = jnp.where(lane // rope == h % side, q_r,
                                jnp.zeros_like(q_r))
            s = (lax.dot_general(q_n, k_n, _NT,
                                 preferred_element_type=jnp.float32)
                 + lax.dot_general(q_r, k_r, _NT,
                                   preferred_element_type=jnp.float32))
            s = jnp.where(allowed, s, _NEG)
            m = m_ref[h]                                    # (bq, 128)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            # m_new is at least _FLOOR, so a score that is not allowed
            # gives an exact 0 in every row, one that has seen no key too
            p = jnp.exp2((s - jnp.concatenate([m_new] * (block // 128), 1))
                         * to_exp2)
            corr = jnp.exp2((m - m_new) * to_exp2)
            lanes = p[:, :128]          # a row's sum, a lane's part of it
            for c in range(128, block, 128):
                lanes = lanes + p[:, c:c + 128]
            l_ref[h] = l_ref[h] * corr + lanes
            acc_ref[h] = acc_ref[h] * corr + lax.dot_general(
                p.astype(v.dtype), v, _NN,
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        for h in range(group):
            total = l_ref[h].sum(axis=-1, keepdims=True)
            o_ref[0, :, h * v_dim:(h + 1) * v_dim] = (
                acc_ref[h] / jnp.maximum(total, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _call(k_len, qn3, qr3, kv3, kr3, mask, scale: float, sizes: tuple):
    """qn3 (B, Tq, H N), qr3 (B, Tq, H P), kv3 (B, Tk, H (N + W)), kr3 (B,
    Tk, 128: copies of the shared key part side by side), mask (B, Tq, Tk)
    int8 -> (B, Tq, H W); Tk whole blocks."""
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    nope, rope, v_dim = sizes
    b, t_q, _ = qn3.shape
    t_k = kv3.shape[1]
    heads = qn3.shape[-1] // nope
    block = min(_BLOCK, t_k)

    def key_block(j, n):
        return jnp.minimum(j, (n[0] - 1) // block)

    return pallas_call(
        functools.partial(_kernel, scale=scale, nope=nope, rope=rope,
                          v_dim=v_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, heads // _GROUP, t_k // block),
            in_specs=[
                pl.BlockSpec((1, t_q, _GROUP * nope),
                             lambda b, g, j, n: (b, 0, g)),
                pl.BlockSpec((1, t_q, _GROUP * rope),
                             lambda b, g, j, n: (b, 0, g)),
                # a group's columns of the up-projection: each head's keys,
                # then its values
                pl.BlockSpec((1, block, _GROUP * (nope + v_dim)),
                             lambda b, g, j, n: (b, key_block(j, n), g)),
                pl.BlockSpec((1, block, 128),
                             lambda b, g, j, n: (b, key_block(j, n), 0)),
                pl.BlockSpec((1, t_q, block),
                             lambda b, g, j, n: (b, 0, key_block(j, n))),
            ],
            out_specs=pl.BlockSpec((1, t_q, _GROUP * v_dim),
                                   lambda b, g, j, n: (b, 0, g)),
            scratch_shapes=[pltpu.VMEM((_GROUP, t_q, 128), jnp.float32),
                            pltpu.VMEM((_GROUP, t_q, 128), jnp.float32),
                            pltpu.VMEM((_GROUP, t_q, v_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, t_q, heads * v_dim), qn3.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="masked_latent_attention",
    )(k_len, qn3, qr3, kv3, kr3, mask)


def masked_latent_attention(q, kv, k_r, mask, scale: float, k_len=None):
    """Softmax attention of q (B, Tq, H, nope + rope) over the keys
    ``[kv[..., :nope], k_r]`` and the values ``kv[..., nope:]``, kv (B, Tk,
    H, nope + v_dim) and k_r (B, Tk, rope) shared by the heads, at the pairs
    ``mask`` (B, Tq, Tk) bool allows.  Scores times ``scale``.  Products in
    q's type, sums float32; (B, Tq, H, v_dim) in q's type, zeros for a query
    that may see no key.  The keys are padded to whole blocks with keys no
    query may see.

    ``k_len`` (an int32 scalar, traced or not, 1 to Tk; None: Tk): no query
    sees a key at or past it, whatever the mask says there, and the key
    blocks past it are neither fetched nor read."""
    b, t_q, heads, width = q.shape
    t_k, rope = kv.shape[1], k_r.shape[-1]
    nope, v_dim = width - rope, kv.shape[-1] - (width - rope)
    if not tiles(t_q, heads, nope, rope, v_dim):
        raise ValueError(
            f"masked_latent_attention: {t_q} queries of {heads} heads {nope} "
            f"+ {rope} and {v_dim} wide do not tile (at most {_ROWS} queries "
            f"in whole 32s, 128 lanes a part, heads in groups of {_GROUP})")
    block = min(_BLOCK, -(-t_k // 128) * 128)
    pad = -t_k % block
    mask = mask.astype(jnp.int8)
    if pad:
        kv = jnp.pad(kv, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_r = jnp.pad(k_r, ((0, 0), (0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, 0), (0, 0), (0, pad)))
    out = _call(jnp.asarray(t_k if k_len is None else k_len,
                            jnp.int32).reshape(1),
                q[..., :nope].reshape(b, t_q, -1),
                q[..., nope:].reshape(b, t_q, -1),
                kv.reshape(b, t_k + pad, -1),
                jnp.tile(k_r, (1, 1, 128 // rope)), mask, float(scale),
                (nope, rope, v_dim))
    return out.reshape(b, t_q, heads, v_dim)
