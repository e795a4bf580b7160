"""Power retention over a whole prompt, read directly: the sums of
``models/retention.py``'s defining equations as one pallas kernel, the
forward pass of a decoder's prefill on TPUs.

With ``c_t = sum_{s<=t} log g_s`` a K/V head (never rising), for a query
head ``h`` over its K/V head:

    num_t = sum_{j<=t} exp(c_t - c_j) (q_t . k_j)^2 / d  v_j
    den_t = sum_{j<=t} exp(c_t - c_j) (q_t . k_j)^2 / d

``retention.chunked`` reads what came before a chunk through the state,
``phi(q_t)`` (8320 wide a head of 128) against ``S`` (8320 x 128): 2.13 MFLOP
a query whatever the chunk, where a key read directly is 512 FLOP.  Under
``retention.CROSSOVER`` positions the direct sums are the cheaper, and as
``jax.numpy`` they would put a head's (T, T) float32 weights through the
HBM several times.  Here a tile of one K/V head's weights stays in VMEM and
the head's keys and values stream past it, as ``ops/latent_attention.py``
does for a softmax; this is simpler than one: a weight is a square times a
decay that factorises over a key block, so there is no running maximum and
no exponential an element.

A grid cell is (sequence, K/V head, tile of ``ROWS`` queries).  The ``R``
query heads of the K/V head read the same keys, values and decays, so the
cell takes them together, one under another as the rows of one left operand
``(R ROWS, d)``: one product with a key block for all of them, one decay a
(query, key) pair shared by the five.  Queries, keys and values are read as
they lie, positions major: a K/V head's queries are ``R`` lane blocks of
``(B, T, H d)`` side by side.  The keys from position 0 to the tile's last
query are visited and none above the diagonal.

**Every exponent is at most zero**, as in ``chunked``, and none is a
difference of large sums: the decays are summed inside a block of ``ROWS``
positions (``l``, inclusive, from the block's first position; ``tot`` a
block's whole), never over the sequence, whose running sum at a few thousand
positions has lost the digits a neighbour's weight needs.  A key block
wholly before the tile weighs its keys ``exp((tot - l_j) + gap)``, the decay
from key ``j`` to its block's end and ``gap`` from there to the tile's start
(the blocks between, added up as the loop walks back from the tile): a row,
one multiply an element beside the square; the tile's rows of what those
blocks add up to are scaled once, ``exp(l_t)``.  The block the diagonal
crosses takes the masked ``exp(l_t - l_j)`` element by element, once for the
``R`` heads.  A weight that underflows is one whose true value is smaller
still.  ``num`` and ``den`` leave the kernel at their true scale (the
quotient's ``eps`` is added to ``den`` outside, in ``retention._quotient``).

Products in the operands' type with float32 sums, as ``chunked``'s; the
decays, their sums, ``num`` and ``den`` float32.  The power of ``q . k`` is the
caller's function (``retention._power``), traced into the body.  Forward
only: a trainer keeps ``chunked``.  The keys and values of one K/V head
enter a cell as one VMEM block each, so a sequence is at most ``MAX_ROWS``
positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["retention_prefill", "tiles", "ROWS", "MAX_ROWS"]

# queries a tile, and keys a block: under R = 5 heads a (1280, 256) float32
# tile of weights, 1.3 MB
ROWS = 256
# two whole-sequence operands a cell, double buffered: 8 MiB of bfloat16.
# The call names no ``vmem_limit_bytes``: at ROWS = 256 under five heads its
# blocks and a tile's temporaries fit what Mosaic gives unasked (16 MiB on
# the v5e) up to this many positions, and a stated limit is reserved whole
# and taken from what XLA may place around the call
MAX_ROWS = 8192
_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b


def tiles(t: int, d: int) -> bool:
    """True where the kernel takes sequences of ``t`` positions of heads
    ``d`` wide: a head one block of 128 lanes, whole tiles of ``ROWS``
    positions (nothing is padded here), at most ``MAX_ROWS``."""
    return d == 128 and 0 < t <= MAX_ROWS and t % ROWS == 0


def _kernel(q_ref, k_ref, v_ref, row_ref, col_ref, num_ref, den_ref, *,
            power, heads: int):
    """One (sequence, K/V head, query tile) cell: the key blocks before the
    tile streamed past all its rows under a row of weights, nearest first,
    then the block the diagonal crosses under the masked decays."""
    from jax import lax

    from ompi_tpu.ops._pallas import pl

    f32 = jnp.float32
    rows, d = col_ref.shape[0], k_ref.shape[-1]
    tile = pl.program_id(2)
    # the R query heads of this K/V head, one under another: (R rows, d)
    q = jnp.concatenate([q_ref[0, :, r * d:(r + 1) * d]
                         for r in range(heads)], axis=0)
    l_t = col_ref[...]                              # (rows, 1)

    def block(j):
        """Key block ``j``: the power of the rows' products with its keys,
        its decays' sums (l_j, tot: rows) and its values."""
        at = pl.ds(pl.multiple_of(j * rows, rows), rows)
        s = lax.dot_general(q, k_ref[0, at, :], _NT,
                            preferred_element_type=f32)
        sums = row_ref[:, at]
        return power(s), sums[0:1], sums[1:2], v_ref[0, at, :]

    def before(n, carry):
        num, den, gap = carry       # gap: from this block's end to the tile
        p, l_j, tot, v = block(tile - 1 - n)
        a = p * (jnp.exp(tot - l_j + gap) * (1.0 / d))      # a row of weights
        return (num + lax.dot_general(a.astype(v.dtype), v, _NN,
                                      preferred_element_type=f32),
                den + a.sum(axis=-1, keepdims=True), gap + tot[:, 0:1])

    num, den, _gap = lax.fori_loop(
        0, tile, before, (jnp.zeros((heads * rows, d), f32),
                          jnp.zeros((heads * rows, 1), f32),
                          jnp.zeros((1, 1), f32)))
    far = jnp.concatenate([jnp.exp(l_t)] * heads, axis=0)
    # the block the diagonal crosses: a decay an element, once for the heads
    p, l_j, _tot, v = block(tile)
    at_or_before = (lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
                    >= lax.broadcasted_iota(jnp.int32, (rows, rows), 1))
    decay = jnp.exp(jnp.where(at_or_before, l_t - l_j, -jnp.inf)) * (1.0 / d)
    a = p * jnp.concatenate([decay] * heads, axis=0)
    num = num * far + lax.dot_general(a.astype(v.dtype), v, _NN,
                                      preferred_element_type=f32)
    den = den * far + a.sum(axis=-1, keepdims=True)
    for r in range(heads):
        mine = slice(r * rows, (r + 1) * rows)
        num_ref[0, :, r * d:(r + 1) * d] = num[mine]
        den_ref[:, r:r + 1] = den[mine]


@functools.partial(jax.jit, static_argnums=(4,))
def _call(q3, k3, v3, logg, power):
    """q3 (B, T, H d), k3 and v3 (B, T, G d), logg (B, T, G) float32 -> num
    (B, T, H d) and den (B, G, T, R) float32; T whole tiles."""
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    b, t, groups = logg.shape
    d = k3.shape[-1] // groups
    heads = q3.shape[-1] // k3.shape[-1]
    # the decays summed inside each block of ROWS positions, a K/V head:
    # along the lanes for a block's keys, beside the block's whole, and down
    # the sublanes for a tile's queries (an operand one lane wide)
    inside = jnp.cumsum(jnp.moveaxis(logg, 1, 2).reshape(
        b, groups, t // ROWS, ROWS), axis=-1)
    whole = jnp.broadcast_to(inside[..., -1:], inside.shape)
    inside, whole = (y.reshape(b, groups, t) for y in (inside, whole))
    sequence = pl.BlockSpec((1, t, d), lambda b, g, i: (b, 0, g))
    return pallas_call(
        functools.partial(_kernel, power=power, heads=heads),
        grid=(b, groups, t // ROWS),
        in_specs=[
            pl.BlockSpec((1, ROWS, heads * d), lambda b, g, i: (b, i, g)),
            sequence, sequence,
            pl.BlockSpec((None, None, 2, t), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((None, None, ROWS, 1), lambda b, g, i: (b, g, i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, ROWS, heads * d), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((None, None, ROWS, heads),
                         lambda b, g, i: (b, g, i, 0))),
        out_shape=(jax.ShapeDtypeStruct(q3.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, groups, t, heads), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        name="retention_prefill",
    )(q3, k3, v3, jnp.stack([inside, whole], axis=2), inside[..., None])


def retention_prefill(q, k, v, logg, power):
    """The direct sums of q (B, T, H, d) over k, v (B, T, G, d), K/V head
    ``g`` serving the query heads ``g H/G .. (g + 1) H/G - 1``, under the
    log decays logg (B, T, G), at most zero; ``power`` is what a product
    ``q . k`` is raised to (a function of the float32 products, traced into
    the kernel).  Returns ``num`` (B, T, G, R, d) and ``den`` (B, T, G, R)
    float32, position t summed over 0 .. t."""
    b, t, h, d = q.shape
    groups = k.shape[2]
    if not tiles(t, d) or h % groups:
        raise ValueError(f"retention_prefill: {t} positions of {h} heads "
                         f"{d} wide over {groups} K/V heads do not tile "
                         f"(heads of 128 lanes, whole tiles of {ROWS} "
                         f"positions, at most {MAX_ROWS})")
    num, den = _call(q.reshape(b, t, h * d), k.reshape(b, t, groups * d),
                     v.reshape(b, t, groups * d), logg.astype(jnp.float32),
                     power)
    return (num.reshape(b, t, groups, h // groups, d),
            jnp.moveaxis(den, 1, 2))
