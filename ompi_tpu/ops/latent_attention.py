"""Causal attention of whole sequences whose keys are wider than their
values, as one pallas kernel: the forward pass of ``models/mla.py``'s
prefill, where a head's key is its own part (``nope`` wide, multiplied out of
the latent) beside a part all heads share (``rope`` wide) and its value is
``v_dim`` wide.

The jnp form (``jnp_form``) materialises every head's (T, T) float32 scores
in HBM (at T = 16,384 and 16 heads 17 GB a sequence); this keeps a tile of
one head's scores in VMEM, streams the head's keys and values and the shared
key part past it and holds the running (max, normaliser, accumulator) of the
tile's queries, as ``ops/flash_attention.py``'s forward does for heads of one
width.  A tile's scores are two products, ``q_n . k_n`` and ``q_r . k_r``, so
the shared part is read as it lies, ``(B, T, rope)``, and never copied out to
the heads; a head's own keys and its values are the two lane blocks of its
``nope + v_dim`` columns of the latent's up-projection ``(B, T, H (nope +
v_dim))``, read in place.  The queries are read as they lie too, positions
major, never transposed to the heads: a head's own part is a lane block of
``(B, T, H nope)``, and its shared part, ``rope`` lanes of ``(B, T, H
rope)``, comes in the 128-lane block it shares with its neighbours, whose
lanes are zeroed before they meet as many copies of ``k_r`` side by side.  A
grid cell is (batch, head, q tile); the keys from position 0 to the cell's
last query are visited and none above the diagonal.

A tile is ``rows_q`` queries by ``rows_k`` keys, ``rows_q`` a multiple of
``rows_k`` (``tile``: both from the length).  The key blocks under the
tile's first query are streamed past all its rows by a loop; each of the
``rows_q / rows_k`` blocks the diagonal crosses meets only the rows at or
under it, a slab that loses its first ``rows_k`` rows (finished, and written
out) a block.  A long sequence takes square tiles, one crossed block a cell;
a short one is a single tile a head whose slabs are the whole causal half,
with no loop at all.

Queries and keys start at position 0 and are as many (a prefill).  The
backward pass is the jnp form's (``jax.custom_vjp``: recomputed from q, kv
and k_r, a sequence's whole scores held), so a trainer may take the kernel's
forward wherever it engages.  The keys of one head enter a cell as one VMEM
block, so a sequence is at most ``MAX_ROWS`` positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["latent_attention", "jnp_form", "tiles", "tile", "vmem_limit",
           "MAX_ROWS"]

_NEG = -1e30
_BLOCK = 512            # rows of q, and of k, a square tile
# three whole-sequence operands a cell (k_n, v, and k_r padded to 128 lanes),
# double buffered: 48 MiB of bfloat16 at this many rows
MAX_ROWS = 32_768
# what a call whose blocks pass the compiler's own allowance states
_VMEM_LIMIT_BYTES = 96 << 20
# what Mosaic gives a kernel that names no limit (the v5e's compiler)
_VMEM_UNASKED_BYTES = 16 << 20
# a sequence of up to this many positions is one tile of queries a head
_ONE_TILE_ROWS = 1024
_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b


def _lanes(n: int) -> int:
    """``n`` rounded up to whole blocks of 128 lanes."""
    return -(-n // 128) * 128


def tiles(t: int, heads: int, nope: int, rope: int, v_dim: int) -> bool:
    """True where the kernel takes sequences of ``t`` positions of ``heads``
    heads whose own key part is ``nope``, whose shared part is ``rope`` and
    whose values are ``v_dim`` wide: the own part and the values a block of
    128 lanes each, the heads' shared parts whole blocks of 128 lanes side by
    side (the positions are padded to whole tiles here)."""
    return (nope == v_dim == 128 and 0 < t <= MAX_ROWS
            and 0 < rope <= 128 and 128 % rope == 0
            and heads % (128 // rope) == 0)


def tile(t: int) -> tuple[int, int]:
    """(rows of q, rows of k) a tile of sequences of ``t`` positions.  Up to
    ``_ONE_TILE_ROWS``: the sequence, padded to whole lanes, is one tile of
    queries and its keys go by blocks of 128, so exactly the 128-row blocks
    at or under the diagonal are multiplied and 896 positions are not padded.
    Longer: square tiles of ``_BLOCK`` rows (the positions padded to whole
    tiles), the diagonal's share of the work being small there."""
    if t <= _ONE_TILE_ROWS:
        return _lanes(t), 128
    return _BLOCK, _BLOCK


def vmem_limit(t: int, rows_q: int, rows_k: int, nope: int, rope: int,
               v_dim: int, itemsize: int = 2) -> int:
    """The VMEM a call of ``t`` (padded) positions states: twice what its
    blocks count (q and the output a tile, the head's keys, the shared key
    part in whole lanes and the values a sequence, each double buffered; a
    tile's float32 scores and their exponentials, and the accumulator) where
    that stays under what the compiler gives unasked; past it the kernel's
    ceiling, sized for ``MAX_ROWS``.  A stated limit is reserved whole and
    taken from what XLA may place around the call (PERF.md section 7, item
    22), so a short sequence does not state a long one's."""
    blocks = 2 * itemsize * (
        rows_q * (_lanes(nope) + _lanes(rope) + _lanes(v_dim))
        + t * (_lanes(nope) + _lanes(rope) + _lanes(v_dim)))
    scores = 4 * rows_q * (2 * rows_k + _lanes(v_dim))
    stated = 2 * (blocks + scores)
    return stated if stated <= _VMEM_UNASKED_BYTES else _VMEM_LIMIT_BYTES


def jnp_form(q, kv, k_r, scale: float):
    """``latent_attention`` in ``jax.numpy``, a sequence's whole (T, T)
    float32 scores held: what runs off TPUs and where the kernel does not
    tile or does not win, and what the kernel's backward pass differentiates.
    The result in float32."""
    f32, t = jnp.float32, q.shape[1]
    nope = q.shape[-1] - k_r.shape[-1]
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope],
                    preferred_element_type=f32)
         + jnp.einsum("bqhd,bkd->bhqk", q[..., nope:], k_r,
                      preferred_element_type=f32)) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    w = jax.nn.softmax(jnp.where(causal, s, _NEG), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(q.dtype), kv[..., nope:],
                      preferred_element_type=f32)


def _kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, *, scale: float,
            rope: int, rows_k: int):
    """One (batch, head, q tile) cell: the key blocks under the tile
    streamed past all its rows, then the blocks the diagonal crosses past
    the rows at or under each; the softmax online in float32."""
    from jax import lax

    from ompi_tpu.ops._pallas import pl

    rows_q = qn_ref.shape[1]
    crossed = rows_q // rows_k      # key blocks the diagonal crosses here
    # the key block of the tile's first query; one tile a head: block 0
    first = 0 if rows_q == kn_ref.shape[1] else pl.program_id(2) * crossed
    q_n, q_r = qn_ref[0], qr_ref[0]             # (bq, N), (bq, 128)
    if rope < q_r.shape[1]:     # the neighbours' lanes: zeros for k_r's copies
        lane = lax.broadcasted_iota(jnp.int32, q_r.shape, 1)
        mine = lane // rope == pl.program_id(1) % (q_r.shape[1] // rope)
        q_r = jnp.where(mine, q_r, jnp.zeros_like(q_r))

    def step(j, carry, lo=None):
        """Key block ``j`` past the tile's rows from ``lo`` on, which are
        what ``carry`` holds; ``lo`` None: all rows, nothing masked."""
        m, l, acc = carry
        at = j * rows_k
        ks = pl.ds(at if isinstance(at, int) else pl.multiple_of(at, rows_k),
                   rows_k)
        v_blk = v_ref[0, ks, :]
        own, shared = (q_n[lo:], q_r[lo:]) if lo else (q_n, q_r)
        s = (lax.dot_general(own, kn_ref[0, ks, :], _NT,
                             preferred_element_type=jnp.float32)
             + lax.dot_general(shared, kr_ref[0, ks, :], _NT,
                               preferred_element_type=jnp.float32)) * scale
        if lo is not None:      # the slab's first query: the block's first key
            rows = lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        return (m_new, l * corr + p.sum(axis=-1),
                acc * corr[:, None] + lax.dot_general(
                    p.astype(v_blk.dtype), v_blk, _NN,
                    preferred_element_type=jnp.float32))

    carry = (jnp.full((rows_q,), _NEG, jnp.float32),
             jnp.zeros((rows_q,), jnp.float32),
             jnp.zeros((rows_q, v_ref.shape[-1]), jnp.float32))
    if not isinstance(first, int):
        carry = lax.fori_loop(0, first, step, carry)
    for d in range(crossed):
        m, l, acc = step(first + d, carry, d * rows_k)
        if d < crossed - 1:     # the slab's first rows have seen their keys
            carry = tuple(x[rows_k:] for x in (m, l, acc))
            l, acc = l[:rows_k], acc[:rows_k]
        o_ref[0, pl.ds(d * rows_k, l.size)] = (
            acc / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _call(qn3, qr3, kv3, kr3, scale: float, sizes: tuple, rows: tuple):
    """qn3 (B, T, H N), qr3 (B, T, H P), kv3 (B, T, H (N + W)), kr3 (B, T,
    128: copies of the shared key part side by side) -> (B, T, H W); T whole
    tiles of ``rows`` (of q, of k)."""
    from ompi_tpu.ops._pallas import pallas_call, pl
    from ompi_tpu.ops._pallas import pltpu

    nope, rope, v_dim = sizes
    rows_q, rows_k = rows
    b, t, shared = kr3.shape
    heads, side = qn3.shape[-1] // nope, shared // rope
    return pallas_call(
        functools.partial(_kernel, scale=scale, rope=rope, rows_k=rows_k),
        grid=(b, heads, t // rows_q),
        in_specs=[
            pl.BlockSpec((1, rows_q, nope), lambda b, h, i: (b, i, h)),
            pl.BlockSpec((1, rows_q, shared),
                         lambda b, h, i: (b, i, h // side)),
            # a head's columns of the up-projection: its keys, then its values
            pl.BlockSpec((1, t, nope), lambda b, h, i: (b, 0, 2 * h)),
            pl.BlockSpec((1, t, shared), lambda b, h, i: (b, 0, 0)),
            pl.BlockSpec((1, t, v_dim), lambda b, h, i: (b, 0, 2 * h + 1)),
        ],
        out_specs=pl.BlockSpec((1, rows_q, v_dim), lambda b, h, i: (b, i, h)),
        out_shape=jax.ShapeDtypeStruct((b, t, heads * v_dim), qn3.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=vmem_limit(t, rows_q, rows_k, nope, rope, v_dim,
                                        qn3.dtype.itemsize)),
        name="latent_attention",
    )(qn3, qr3, kv3, kr3, kv3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(q, kv, k_r, scale: float, rows: tuple):
    """The kernel on q (B, T, H, N + P), kv (B, T, H, N + W), k_r (B, T, P)
    of whole tiles -> (B, T, H W); its cotangents are the jnp form's."""
    b, t, _heads, width = q.shape
    rope = k_r.shape[-1]
    nope = width - rope
    return _call(q[..., :nope].reshape(b, t, -1),
                 q[..., nope:].reshape(b, t, -1), kv.reshape(b, t, -1),
                 jnp.tile(k_r, (1, 1, 128 // rope)), scale,
                 (nope, rope, kv.shape[-1] - nope), rows)


def _attend_fwd(q, kv, k_r, scale, rows):
    return _attend(q, kv, k_r, scale, rows), (q, kv, k_r)


def _attend_bwd(scale, rows, saved, g):
    out, pull = jax.vjp(lambda q, kv, k_r: jnp_form(q, kv, k_r, scale),
                        *saved)
    return pull(g.reshape(out.shape).astype(out.dtype))


_attend.defvjp(_attend_fwd, _attend_bwd)


def latent_attention(q, kv, k_r, scale: float, rows: tuple | None = None):
    """Causal softmax attention of q (B, T, H, nope + rope) over the keys
    ``[kv[..., :nope], k_r]`` and the values ``kv[..., nope:]``, kv (B, T, H,
    nope + v_dim) and k_r (B, T, rope) shared by the heads; position t sees
    positions 0 .. t.  Scores times ``scale``.  Products in q's type, sums
    float32; (B, T, H, v_dim) in q's type.  ``rows``: (rows of q, rows of k)
    a tile, ``tile(T)`` unless a test or a measurement names another.  The
    positions are padded to whole tiles with keys no query sees and queries
    that are dropped."""
    t, heads, width, rope = *q.shape[1:3], q.shape[-1], k_r.shape[-1]
    nope, v_dim = width - rope, kv.shape[-1] - (width - rope)
    if not tiles(t, heads, nope, rope, v_dim):
        raise ValueError(f"latent_attention: {t} positions of {heads} heads "
                         f"{nope} + {rope} and {v_dim} wide do not tile (128 "
                         f"lanes a part, the heads' shared parts whole blocks "
                         f"of 128, at most {MAX_ROWS} positions)")
    rows_q, rows_k = rows or tile(t)
    if rows_q % rows_k or rows_k % 128:
        raise ValueError(f"latent_attention: a tile of {rows_q} queries by "
                         f"{rows_k} keys (whole lanes of keys, and whole "
                         f"blocks of them a tile of queries)")
    pad = -t % rows_q
    if pad:
        q, kv = (jnp.pad(y, ((0, 0), (0, pad), (0, 0), (0, 0)))
                 for y in (q, kv))
        k_r = jnp.pad(k_r, ((0, 0), (0, pad), (0, 0)))
    out = _attend(q, kv, k_r, float(scale), (rows_q, rows_k))
    return out[:, :t].reshape(q.shape[0], t, heads, v_dim)
